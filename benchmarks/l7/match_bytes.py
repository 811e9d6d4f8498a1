"""Bytes the L7 match has to read, from the request's and the rule
tensors' layout alone.

A row brings its request: the path as the tokenizer cut it, 64 bytes, a
method word and the word that names its cell's rule set (4 bytes each).
A rule set is ``R`` rules, and a rule is its path prefix (64 bytes, zero
padded), its method (1 byte, 255 for any), the prefix's length (an int32)
and whether the slot holds a rule (1 byte): 70 bytes. The rule tensors
hold ``n_sets + 1`` sets (set 0 is "none").

This counts **what any implementation must read**, not what the program
does: the program gathers its set's ``R`` rules for every row (``rows * R
* 70`` bytes a batch), but 1,024 rows together then read more than the
tensors hold (42 KB at 200 sets of 3), and a kernel that keeps the rules
on the chip reads them once a batch. So a batch is charged its rows and
the smaller of the two: ``min(rows * R * 70, bytes of the rule tensors)``.
Charging the gathers as written would let such a kernel read over 100% of
its roofline by the count's fault.

Nor is the path dictionary's second upload counted (it crosses the host's
link, not the chip's memory roofline), nor the dictionary's gather on the
device: a wire that ships the 64 bytes a row reads the same 64.

This file imports nothing of the program: it is the yardstick's count.
``tests/test_l7http_config.py`` holds it equal to the shapes
``cilium_tpu.compile.l7`` builds.
"""

PATH_BYTES = 64
WORD_BYTES = 4
#: a row's request: the path, the method's word, the set's word
ROW_BYTES = PATH_BYTES + 2 * WORD_BYTES
#: a rule: path prefix, method (uint8), prefix length (int32), valid (bool)
RULE_BYTES = PATH_BYTES + 1 + WORD_BYTES + 1


def rule_tensor_bytes(n_sets: int, rules_a_set: int) -> int:
    """Bytes of the four rule tensors together: ``n_sets`` sets and the
    empty set 0, ``rules_a_set`` rule slots each."""
    return (n_sets + 1) * rules_a_set * RULE_BYTES


def match_bytes(rows: float, batches: int, n_sets: int,
                rules_a_set: int) -> float:
    """Bytes the matches of ``rows`` requests dispatched in ``batches``
    equal batches have to read: every row's request, and a batch the
    rules its rows name, the whole rule tensors at most."""
    if batches <= 0 or rows <= 0:
        return 0.0
    a_batch = rows / batches
    rules = min(a_batch * rules_a_set * RULE_BYTES,
                rule_tensor_bytes(n_sets, rules_a_set))
    return rows * ROW_BYTES + batches * rules
