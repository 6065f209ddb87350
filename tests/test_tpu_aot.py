"""AOT-compile the full ResNet9 sketched round FOR THE TPU, without one.

The installed libtpu can describe a v5e host it does not own
(``jax.experimental.topologies``), and ``jit(f).trace(...).lower(
lowering_platforms=("tpu",)).compile()`` on those devices gives Mosaic's and
XLA:TPU's verdict on the real kernels at the real geometry. This is the
guard the forced-8-device CPU mesh can never be: every Pallas kernel is gated
off off-TPU, so "Mosaic kernels cannot be automatically partitioned" (the
replicated server phase calling Pallas under a mesh-sharded jit outside any
shard_map) only ever shows at a TPU lowering. Compile only — nothing runs.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from commefficient_tpu import models
from commefficient_tpu.federated.losses import make_cv_losses
from commefficient_tpu.federated.rounds import (
    RoundConfig,
    build_round_step,
    init_client_states,
)
from commefficient_tpu.federated.server import ServerConfig, init_server_state
from commefficient_tpu.federated.worker import WorkerConfig
from commefficient_tpu.ops import attention, sketch as sketch_ops
from commefficient_tpu.ops.flat import ravel_pytree
from commefficient_tpu.parallel.mesh import default_client_mesh
from commefficient_tpu.telemetry import log_magnitude_histogram

W, BS = 8, 8


def _v5e_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any libtpu refusal is a skip
        pytest.skip(f"libtpu cannot describe the v5e:2x2 topology here: "
                    f"{type(e).__name__}: {e}")
    return list(topo.devices)


@pytest.fixture
def kernels_on(monkeypatch):
    """Dispatch as on a TPU: kernels on, the (executing) one-time
    self-checks marked done."""
    monkeypatch.setattr("commefficient_tpu.utils.is_tpu_backend",
                        lambda: True)
    for flag in ("_ESTIMATES_KERNEL_CHECKED", "_SKETCH_KERNEL_CHECKED",
                 "_FUSED_EPILOGUE_CHECKED"):
        monkeypatch.setattr(sketch_ops, flag, True)


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile_tpu(fn, *args):
    traced = fn.trace(*args)
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    return traced, compiled


@pytest.mark.slow
@pytest.mark.parametrize("n_devices,server_shard",
                         [(1, False), (4, False), (4, True)])
def test_resnet9_sketched_round_compiles_for_v5e(kernels_on, n_devices,
                                                 server_shard):
    """The FetchSGD headline round (d=6,568,640, 8x8, 5x500k, k=50k), as
    cv_train dispatches it: client_step then server_step with the default
    telemetry vector, plus the fused train_step."""
    devices = _v5e_devices()[:n_devices]
    model = models.ResNet9()
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 32, 32, 3)), train=False),
        jax.random.key(0))
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)["params"]
    flat, unravel = ravel_pytree(params)
    d = int(flat.size)
    assert d == 6_568_640, f"ResNet9 geometry drifted: d={d}"

    wcfg = WorkerConfig(mode="sketch", error_type="virtual", k=50_000,
                        num_workers=W, weight_decay=5e-4)
    scfg = ServerConfig(mode="sketch", error_type="virtual", k=50_000,
                        grad_size=d, virtual_momentum=0.9)
    sketch = sketch_ops.make_sketch(d, c=500_000, r=5, seed=42,
                                    num_blocks=20)
    cfg = RoundConfig(worker=wcfg, server=scfg, grad_size=d,
                      server_shard=server_shard, telemetry=True,
                      telemetry_hist=True)
    mesh = default_client_mesh(W, devices=devices)
    assert dict(mesh.shape) == {"clients": n_devices}
    loss_train, loss_val = make_cv_losses(model)
    steps = build_round_step(loss_train, loss_val, unravel,
                             lambda t: ravel_pytree(t)[0], cfg,
                             sketch=sketch, mesh=mesh)
    rep = NamedSharding(mesh, P())
    batch = _sds({
        "inputs": jnp.zeros((W, BS, 32, 32, 3), jnp.float32),
        "targets": jnp.zeros((W, BS), jnp.int32),
        "mask": jnp.ones((W, BS), jnp.float32),
        "client_ids": jnp.arange(W, dtype=jnp.int32),
        "worker_mask": jnp.ones(W, jnp.float32),
    }, NamedSharding(mesh, P("clients")))
    ps = _sds(flat, rep)
    server_state = _sds(init_server_state(scfg, sketch), rep)
    client_states = _sds(init_client_states(10, d, wcfg), rep)
    lr, rng = 0.1, jax.random.key(0)

    traced, compiled = _compile_tpu(steps.client_step, ps, client_states, {},
                                    batch, lr, rng)
    # the client's one sketch pass: a Mosaic accumulate call per group of
    # the leaf plan (docs/stream_sketch.md), and no zero-init sketch call
    assert steps.client_sketch_path == "leaf_groups"
    calls = [ln for ln in compiled.as_text().splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert sum("fed_sketch_accum" in ln for ln in calls) \
        == steps.client_sketch_launches == 5, calls
    assert not any("fed_sketch_vec" in ln for ln in calls)
    # the server phase consumes the client phase's outputs where the
    # compiler left them
    ctx = jax.tree_util.tree_map(
        lambda info, sh: jax.ShapeDtypeStruct(info.shape, info.dtype,
                                              sharding=sh),
        traced.out_info[0], compiled.output_shardings[0])
    _compile_tpu(steps.server_step, ps, server_state, client_states, ctx,
                 lr, rng)
    _compile_tpu(steps.train_step, ps, server_state, client_states, {},
                 batch, lr, rng)


@pytest.mark.slow
def test_histogram_is_one_pass_on_v5e():
    """Telemetry's histogram over GPT-2's d = 124,444,417 as XLA:TPU
    compiles it: no scatter (the chip runs one serially, 8.7 ns an element)
    and no temporary near the operand's 0.46 GiB — the binning and the
    eight counts are one fusion that reads the vector once."""
    d = 124_444_417
    x = jax.ShapeDtypeStruct(
        (d,), jnp.float32, sharding=SingleDeviceSharding(_v5e_devices()[0]))
    _, compiled = _compile_tpu(jax.jit(log_magnitude_histogram), x)
    assert not re.search(r"\bscatter\(", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("S,T", [(8, 512), (4, attention.MAX_FUSED_T)],
                         ids=["cell4", "longest"])
def test_attention_kernels_compile_for_v5e(S, T, backward):
    """The latent attention's fused core (ops/attention.py) at
    ``joyai_flash_sketch_1c``'s shape (8 sequences x 512 positions x 32
    heads of 128 + 64 / 128) and at the longest sequence the path chooser
    sends to the kernels: Mosaic takes the forward kernel and the backward
    kernel (their blocks fit VMEM), and nothing of the scores' size (268 MB
    in float32 in the cell) is left in HBM around them."""
    H = 32
    one = SingleDeviceSharding(_v5e_devices()[0])
    # the projections' outputs: 3-D, viewed (S, T, H, d) without a copy
    q, q_r, kv, k_r, d_out = (
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
        for shape in ((S, T, H * 192), (S, T, H * 64), (S, T, H * 256),
                      (S, T, 64), (S, T, H * 128)))

    def fwd(q, q_r, kv, k_r):
        heads = (lambda x: x.reshape(S, T, H, -1))
        return attention.mla_attention_fused(
            heads(q), heads(q_r), heads(kv), k_r).reshape(S, T, -1)

    def bwd(q, q_r, kv, k_r, d_out):
        return jax.vjp(fwd, q, q_r, kv, k_r)[1](d_out)

    _, compiled = _compile_tpu(jax.jit(bwd if backward else fwd),
                               *((q, q_r, kv, k_r)
                                 + ((d_out,) if backward else ())))
    text = compiled.as_text()
    assert "fed_mla_attn_fwd" in text
    assert ("fed_mla_attn_bwd" in text) == backward
    # nothing of the scores' size, and no copy of an operand either: the
    # kernels read q and kv as they are
    assert compiled.memory_analysis().temp_size_in_bytes < S * T * H * 192 * 4


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("Hq,window", [(48, None), (64, 512)],
                         ids=["full48", "window64"])
def test_gqa_attention_kernels_compile_for_v5e(Hq, window, backward):
    """The grouped-query core (ops/attention.py) at
    ``laguna_xs2_sketch_1c``'s shape: 4 sequences x 4,096 positions (the
    longest the path chooser sends to the kernels), 48 query heads with no
    window (32 rotary pairs: two lane rotations a head) or 64 with a window
    of 512 (64 pairs: one) over 8 key/value heads of 128, operands flat as
    the projections write them. Mosaic takes both kernels with the turn and
    the gate inside (their blocks fit VMEM, the rotations lower) and nothing
    of the scores' size (4.3 GB a sequence of a 64-head layer in float32) is
    left in HBM around them: beside the gated output only the ungated one
    (the backward's residual), and the log-sum-exp and the gates by
    key/value head, lane-padded (4, 8, 4096, G) float32 arrays of 64 MiB."""
    S, T, Hkv, d = 4, attention.MAX_GQA_T, 8, 128
    half = d // 4 if window is None else d // 2
    one = SingleDeviceSharding(_v5e_devices()[0])
    q, k, v, gate, cos, sin, d_out = (
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
        for shape in ((S, T, Hq * d), (S, T, Hkv * d), (S, T, Hkv * d),
                      (S, T, Hq), (T, half), (T, half), (S, T, Hq * d)))

    def fwd(q, k, v, gate, cos, sin):
        return attention.gqa_attention_fused(q, k, v, gate, (cos, sin),
                                             window)

    def bwd(q, k, v, gate, cos, sin, d_out):
        return jax.vjp(lambda *a: fwd(*a, cos, sin), q, k, v, gate)[1](d_out)

    _, compiled = _compile_tpu(jax.jit(bwd if backward else fwd),
                               *((q, k, v, gate, cos, sin)
                                 + ((d_out,) if backward else ())))
    text = compiled.as_text()
    assert "fed_gqa_attn_fwd" in text
    assert ("fed_gqa_attn_bwd" in text) == backward
    # the ungated output, the log-sum-exp, the gates (and the backward's
    # gate gradient before its way back), the rope's table: never a (T, T)
    # array, and no second array of q's size
    out_bytes = S * T * Hq * d * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < out_bytes + ((3 * 64 + 8) << 20)


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("T", [1024, attention.MAX_GQA_T])
def test_gqa_attention_kernels_without_a_gate_compile_for_v5e(T, backward):
    """The same kernels without the gate's operand (``gate=None``) at
    models/ouro.py's shape: 16 query heads over 16 key/value heads of 128 (a
    group of ONE query head a grid step: q's block is one head wide, the row
    statistics' blocks one column), every column turned, 4 sequences of
    1,024 positions (``ouro_2p6b_sketch_1c``'s scan step) and of 4,096 (the
    longest the path chooser sends them). Forward: one output and the
    log-sum-exp. Backward: ``dq``, ``dk``, ``dv`` and no gate gradient.
    Nothing of the scores' size and no second array of q's size is left in
    HBM around them: the log-sum-exp by key/value head is a lane-padded
    (4, 16, T, 1) float32 array (128 MiB at 4,096 positions)."""
    S, H, d = 4, 16, 128
    one = SingleDeviceSharding(_v5e_devices()[0])
    q, cos, sin = (jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
                   for shape in ((S, T, H * d), (T, d // 2), (T, d // 2)))

    def fwd(q, k, v, cos, sin):
        return attention.gqa_attention_fused(q, k, v, None, (cos, sin),
                                             heads=H)

    def bwd(q, k, v, cos, sin, d_out):
        return jax.vjp(lambda *a: fwd(*a, cos, sin), q, k, v)[1](d_out)

    _, compiled = _compile_tpu(jax.jit(bwd if backward else fwd),
                               *((q, q, q, cos, sin)
                                 + ((q,) if backward else ())))
    text = compiled.as_text()
    assert "fed_gqa_attn_fwd" in text
    assert ("fed_gqa_attn_bwd" in text) == backward
    if backward:
        (call,) = [line for line in text.splitlines()
                   if "fed_gqa_attn_bwd" in line and "custom-call(" in line]
        # three results (dq, dk, dv): no fourth for a gate
        assert call.count("f32[4,%d,2048]" % T) >= 3
        assert "f32[4,16,%d,1]" % T not in call.split(" custom-call(")[0]
    # the output (the backward's residual), the log-sum-exp, the rope's
    # table: never a (T, T) array, and no second array of q's size
    out_bytes = S * T * H * d * 4
    lse_bytes = S * H * T * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < out_bytes + lse_bytes + (8 << 20)


def _entry_ops(text):
    """(element count of the first result, operation, line) of the entry
    computation's instructions of a compiled module's text."""
    entry = text[text.index("ENTRY "):]
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \(?\w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m:
            count = 1
            for n in filter(None, m.group(1).split(",")):
                count *= int(n)
            yield count, m.group(2), line


# temp_size_in_bytes of the same module (forward + backward, S = 4, T =
# 4,096) at the parent of PR 33, where ``GQA.__call__`` viewed q, k, the
# output and their gradients (S, T, H, 128) around the kernels
GQA_MODULE_TEMP_AT_PARENT = {0: 2_493_432_320, 1: 2_898_553_344}


@pytest.mark.parametrize("layer", [0, 1], ids=["full48", "window64"])
def test_gqa_module_leaves_no_relayout_of_q_for_v5e(layer, monkeypatch):
    """One whole ``GQA`` module of models/laguna.py, projections to ``W_o``,
    forward and backward, at ``laguna_xs2_sketch_1c``'s shape: what is left
    of q's size (384 MiB at 48 heads, 512 MiB at 64) in the compiled
    program is what the projections' products (q, and ``W_o``'s backward:
    the gated output's gradient) and the two kernels write. No ``copy``,
    ``reshape`` or ``transpose`` and no other fusion's output: the turn, the
    gate and their backward work on the kernels' tiles, and no
    (S, T, H, 128) view of q exists between the projection and ``W_o``."""
    from commefficient_tpu.models.laguna import GQA, LagunaConfig

    cfg = LagunaConfig()
    S, T = 4, attention.MAX_GQA_T
    Hq = cfg.num_attention_heads_per_layer[layer]
    one = SingleDeviceSharding(_v5e_devices()[0])
    mod = GQA(cfg, layer)
    x = jax.ShapeDtypeStruct((S, T, cfg.hidden_size), jnp.float32,
                             sharding=one)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(mod.init, jax.random.key(0),
                       jnp.zeros((1, 8, cfg.hidden_size)))["params"])

    def both(p, x, d_out):
        out, vjp = jax.vjp(lambda p, x: mod.apply({"params": p}, x), p, x)
        return out, vjp(d_out)

    monkeypatch.setattr(attention, "is_tpu_backend", lambda: True)
    _, compiled = _compile_tpu(jax.jit(both), params, x, x)
    text = compiled.as_text()
    assert "fed_gqa_attn_fwd" in text and "fed_gqa_attn_bwd" in text
    q_size = S * T * Hq * cfg.head_dim
    left = [(op, line) for count, op, line in _entry_ops(text)
            if count >= q_size and op not in ("parameter", "tuple")]
    for op, line in left:
        assert (op == "get-tuple-element" and "fed_gqa_attn" in line) \
            or (op == "fusion" and _is_product(text, line)), line[:300]
    # the kernels' three (gated and ungated output, dq) and the products'
    # two (q, the gated output's gradient): the reader reads something
    assert sorted(op for op, _ in left) == ["fusion"] * 2 \
        + ["get-tuple-element"] * 3
    assert compiled.memory_analysis().temp_size_in_bytes \
        < GQA_MODULE_TEMP_AT_PARENT[layer]


def _is_product(text, line):
    """Whether the computation a fusion instruction calls holds a matrix
    product."""
    name = re.search(r"calls=(%[\w.\-]+)", line).group(1)
    start = text.index("\n" + name + " ")
    return " convolution(" in text[start:text.index("\n}", start)]
