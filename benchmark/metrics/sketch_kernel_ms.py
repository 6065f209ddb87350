"""L4 kernels: device time of the Mosaic (Pallas) custom calls, per round of
the traced window. The kernels carry no stable ``name=``; the trace names an
operation by its HLO text, in which a Pallas call reads
``%<jitted function>.N = ... custom-call(...), custom_call_target=
"tpu_custom_call"`` (``_sketch_vec_pallas``, ``_estimates_pallas``,
``_count_ge_pallas`` today). XLA's own custom calls and fusions that merely
take one as an operand are not counted."""

import re

HEAD = re.compile(r"pallas", re.I)


def is_kernel(name: str) -> bool:
    head = name.split(" = ", 1)[0]
    return 'custom_call_target="tpu_custom_call"' in name or bool(
        HEAD.search(head))


def seconds(ctx, also=None):
    ops = ctx["tr"].op_seconds(
        ctx["trace"], ctx["lo"], ctx["hi"],
        lambda n: is_kernel(n) and (also is None or also(n)))
    return sum(ops.values())


def read(ctx):
    s = seconds(ctx)
    return s / ctx["rounds"] * 1e3 if s and ctx["rounds"] else None
