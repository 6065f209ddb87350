"""Device: the process's ``peak_bytes_reserved`` at the window's last
drain: what the loaded programs reserve for their temporaries (the
activations). The other addend of ``peak_hbm_gib``."""

import _lifecycle


def read(ctx):
    return _lifecycle.gib(_lifecycle.drain_memory(ctx),
                          "peak_bytes_reserved")
