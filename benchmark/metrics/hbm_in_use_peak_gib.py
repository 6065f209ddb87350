"""Device: the process's ``peak_bytes_in_use`` at the window's last
drain (the program's ``memory_sample`` on the ``drain`` event): buffers
alive at once: weights, server state, tables, the batch, results in
flight. One addend of ``peak_hbm_gib``."""

import _lifecycle


def read(ctx):
    return _lifecycle.gib(_lifecycle.drain_memory(ctx), "peak_bytes_in_use")
