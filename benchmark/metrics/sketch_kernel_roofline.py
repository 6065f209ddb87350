"""L4 kernels: the least time the chip's memory system needs for a round's
sketch work over the time its kernels took. The work is the algorithm's, not
a kernel's: accumulate the round's gradient into the table, estimate every
coordinate from the table, re-sketch the k-sparse update."""

import re

import sketch_kernel_ms

# the kernels that do the sketch's work (the top-k count kernels are Mosaic
# calls too, but their bytes are not the sketch's)
SKETCH = re.compile(r"sketch|estimates|epilogue", re.I)


def sketch_bytes(d, r, c, k):
    """Least bytes a round's sketch work must move (float32): accumulate
    reads d and writes r*c_pad; estimates read r*c_pad and write d; the
    re-sketch reads k values with their indices and writes r*c_pad. The
    clients' gradients are summed before the sketch (it is linear), so the
    count of clients does not enter."""
    c_pad = -(-int(c) // 128) * 128
    table = r * c_pad
    return 4 * (d + table) + 4 * (table + d) + (8 * k + 4 * table)


def read(ctx):
    s = sketch_kernel_ms.seconds(
        ctx, lambda n: bool(SKETCH.search(n.split(" = ", 1)[0])))
    if not s or not ctx["rounds"]:
        return None
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise SystemExit(f"bench: no published peak for device_kind {kind!r}")
    p = ctx["params"]
    need = sketch_bytes(ctx["grad_size"], p["num_rows"], p["num_cols"],
                        p["k"]) * ctx["rounds"]
    return need / ctx["peaks"][kind]["hbm_bytes_per_s"] / s * 100.0
