"""The latent attention's fused core (ops/attention.py) against its
``einsum`` path, on the CPU with the kernels interpreted:

- forward and the gradients of all four operands (``q`` with its
  position-free columns, the turned ``q_r``, ``kv`` = keys and values, the
  shared ``k_r``), over sequence lengths and batch sizes (under ``highest``
  the kernels multiply in float32, so that the two agree to rounding);
- the causal boundary, ``dk_r`` as the sum over heads, the same gradients
  under recomputation (``jax.checkpoint``, what ``nn.remat`` is);
- bfloat16 multiplicands against the ``einsum``s on rounded operands;
- which path a call takes, and that the counter says so;
- ``MLA`` through the fused kernels against attention written out per head.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from commefficient_tpu.models import joyai
from commefficient_tpu.models.joyai import JoyAIConfig
from commefficient_tpu.ops import attention as at

from test_joyai import mla_case, per_head_attention

H, DN, DR, DV = 2, 128, 64, 128
SHAPES = [(T, S) for T in (128, 256, 384) for S in (1, 3)]
NAMES = ("q", "q_r", "kv", "k_r")


def inputs(S, T, seed=0, heads=H):
    """The four operands and a cotangent for the output."""
    keys = jax.random.split(jax.random.key(seed), 5)
    shapes = [(S, T, heads, DN + DR), (S, T, heads, DR),
              (S, T, heads, DN + DV), (S, T, DR), (S, T, heads, DV)]
    *x, w = [jax.random.normal(k, s) for k, s in zip(keys, shapes)]
    return x, w


def fused(*x, precision="highest"):
    """The kernels interpreted; float32 multiplicands under ``highest``,
    bfloat16 ones at the default precision (``None``), as on the chip."""
    with jax.default_matmul_precision(precision):
        return at.mla_attention_fused(*x, interpret=True)


def grads(fn, x, w):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=range(4))(*x)


def assert_close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=tol * max(scale, 1.0))


@pytest.mark.parametrize("T,S", SHAPES)
def test_forward_matches_einsum(T, S):
    x, _ = inputs(S, T)
    assert_close(fused(*x), at.mla_attention_einsum(*x))


@pytest.mark.parametrize("T,S", SHAPES)
def test_gradients_match_einsum(T, S):
    x, w = inputs(S, T, seed=1)
    got_all = grads(fused, x, w)
    for name, got, want in zip(NAMES, got_all,
                               grads(at.mla_attention_einsum, x, w)):
        assert got.shape == want.shape, name
        assert_close(got, want)
    # the columns of q that the rotation replaces are not read
    assert not np.any(np.asarray(got_all[0][..., DN:]))


@pytest.mark.parametrize("t", [1, 127, 128, 200])
def test_causal_boundary(t):
    """A change at position t (every input) leaves the outputs before t as
    they were and moves the output at t."""
    x, _ = inputs(2, 256, seed=2)
    moved = [a.at[:, t].add(1.0) for a in x]     # all four operands
    a, b = fused(*x), fused(*moved)
    np.testing.assert_array_equal(np.asarray(a[:, :t]), np.asarray(b[:, :t]))
    assert float(jnp.max(jnp.abs(a[:, t] - b[:, t]))) > 1e-3


def test_dk_r_is_the_sum_over_heads():
    x, w = inputs(2, 256, seed=3)
    q, q_r, kv, k_r = x

    def per_head_keys(k_r_heads):       # (S, T, H, dr): a copy a head
        return sum(
            jnp.pad(at.mla_attention_einsum(
                q[:, :, h:h + 1], q_r[:, :, h:h + 1], kv[:, :, h:h + 1],
                k_r_heads[:, :, h]),
                ((0, 0), (0, 0), (h, H - 1 - h), (0, 0)))
            for h in range(H))

    copies = jnp.broadcast_to(k_r[:, :, None], q_r.shape)
    by_head = jax.grad(lambda k: jnp.sum(per_head_keys(k) * w))(copies)
    assert float(jnp.max(jnp.abs(by_head[:, :, 0] - by_head[:, :, 1]))) > 1e-2
    assert_close(grads(fused, x, w)[3], by_head.sum(axis=2))


@pytest.mark.parametrize("T,S", [(128, 1), (384, 3)])
def test_gradients_under_recomputation(T, S):
    x, w = inputs(S, T, seed=4)
    for got, want in zip(grads(jax.checkpoint(fused), x, w),
                         grads(at.mla_attention_einsum, x, w)):
        assert_close(got, want)


def test_bfloat16_multiplicands():
    """The chip's precision: operands rounded to bfloat16, everything else
    float32; close to the einsums on rounded operands, and not the float32
    result."""
    x, w = inputs(2, 256, seed=5)
    rounded = [a.astype(jnp.bfloat16).astype(jnp.float32) for a in x]
    want = at.mla_attention_einsum(*rounded)
    got = fused(*x, precision=None)
    assert got.dtype == jnp.float32
    assert_close(got, want, tol=1e-2)
    assert float(jnp.max(jnp.abs(got - fused(*x)))) > 1e-4
    for g, e in zip(grads(lambda *a: fused(*a, precision=None), x, w),
                    grads(at.mla_attention_einsum, rounded, w)):
        assert g.dtype == jnp.float32
        assert_close(g, e, tol=2e-2)


CHOICES = {
    # name: (T, heads and widths, tpu backend, precision set, interpret)
    "cpu": (512, (32, 128, 64, 128), False, None, False, "einsum"),
    "tpu": (512, (32, 128, 64, 128), True, None, False, "fused"),
    "interpreted": (512, (32, 128, 64, 128), False, None, True, "fused"),
    "tpu_precision_set": (512, (32, 128, 64, 128), True, "highest", False,
                          "einsum"),
    "tpu_rehearsal_T32": (32, (32, 128, 64, 128), True, None, False,
                          "einsum"),
    "tpu_T_not_a_tile": (200, (32, 128, 64, 128), True, None, False,
                         "einsum"),
    "tpu_T_above_the_kernels": (at.MAX_FUSED_T + at.TILE,
                                (32, 128, 64, 128), True, None, False,
                                "einsum"),
    "tpu_tiny_widths": (128, (2, 16, 8, 16), True, None, False, "einsum"),
    "tpu_odd_heads": (128, (3, 128, 64, 128), True, None, False, "einsum"),
}


@pytest.mark.parametrize("case", sorted(CHOICES))
def test_path_chooser(case, monkeypatch):
    T, widths, tpu, precision, interpret, want = CHOICES[case]
    monkeypatch.setattr(at, "is_tpu_backend", lambda: tpu)
    with jax.default_matmul_precision(precision):
        assert at.attention_path(T, *widths, interpret=interpret) == want


@pytest.mark.parametrize("T,interpret,want", [(32, True, "einsum"),
                                              (128, False, "einsum"),
                                              (128, True, "fused")])
def test_counter_names_the_path_taken(T, interpret, want):
    x, _ = inputs(1, T, seed=6)
    before = dict(at.PATH_CALLS)
    with jax.default_matmul_precision("highest"):
        out = at.mla_attention(*x, interpret=interpret)
        assert_close(out, at.mla_attention_einsum(*x))
    other = "einsum" if want == "fused" else "fused"
    assert at.PATH_CALLS[want] == before[want] + 1
    assert at.PATH_CALLS[other] == before[other]


def test_mla_through_fused_kernels_matches_per_head_attention(monkeypatch):
    """The module at the published head widths (128 + 64 / 128), the fused
    kernels interpreted, against test_joyai's head-by-head oracle."""
    monkeypatch.setattr(joyai, "mla_attention",
                        functools.partial(at.mla_attention, interpret=True))
    cfg = JoyAIConfig(hidden_size=64, num_attention_heads=2, q_lora_rank=32,
                      kv_lora_rank=16, intermediate_size=128,
                      moe_intermediate_size=32, n_routed_experts=16,
                      num_experts_per_tok=4)
    before = at.PATH_CALLS["fused"]
    with jax.default_matmul_precision("highest"):
        x, p, got = mla_case(cfg, 2, 128)
        assert at.PATH_CALLS["fused"] > before
        want = per_head_attention(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
