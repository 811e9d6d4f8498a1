"""Load generator: how long the host stopped the generator's own thread
while it was injecting (warm-up and window), as the summed length of the
native loop's gaps over 1 ms. The loop spins and takes no lock, so such a
gap is the machine's; a stop of T seconds makes T x rate frames late, and
what the ring cannot hold of them is ``nic.refused_in_stop``, not
``failed``."""


def read(run):
    if run.due is None:
        return None
    return run.nic["stop_s"] * 1e3
