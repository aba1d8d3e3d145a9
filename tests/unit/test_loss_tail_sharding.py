"""The loss tail under a sharded mesh: the head is a gathered weight and the
tokens stay on their own chips.

``choose_pspec`` shards the tied table ``[V, D]`` on the hidden dimension
when nothing divides the vocabulary; that placement must not become the
shape of the computation (no ``[chunk, V]`` block may cross chips), and the
sharded loss tail must be the single-device one to float32 rounding.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.models.transformer import causal_lm, cross_entropy
from deepspeed_tpu.runtime.zero.partition import choose_pspec

from tests.unit.hlo_text import collectives, program_text

def _model(mesh, tied, chunk, V, D, **kw):
    return causal_lm("gpt2-small" if tied else "llama-tiny", mesh=mesh,
                     num_layers=1, hidden_size=D, num_heads=2, num_kv_heads=2,
                     vocab_size=V,
                     ce_chunk=chunk, **kw)


def _tail(model, tied):
    def f(head, fnorm, x, labels, mask, bias):
        return model._loss_tail(fnorm, head.T if tied else head, x, labels,
                                mask, head_bias=bias)
    return f


def _parent_formula(model, tied):
    """The loss tail as it was before the last position was masked:
    ``h[:, :-1]`` against ``labels[:, 1:]``, dense, on one device."""
    from deepspeed_tpu.models.layers import norm
    cfg = model.config

    def f(head, fnorm, x, labels, mask, bias):
        h = norm(x, fnorm, cfg.norm, cfg.norm_eps, None)
        logits = h[:, :-1] @ (head.T if tied else head).astype(h.dtype)
        if bias is not None:
            logits = logits + bias.astype(logits.dtype)
        return cross_entropy(logits, labels[:, 1:], z_loss=cfg.z_loss,
                             mask=None if mask is None else mask[:, 1:])
    return f


def _inputs(rng, tied, B, S, D, V, with_mask, with_bias, norm_bias):
    ks = jax.random.split(rng, 6)
    head = jax.random.normal(ks[0], (V, D) if tied else (D, V)) * 0.2
    fnorm = {"scale": 1.0 + 0.1 * jax.random.normal(ks[1], (D,))}
    if norm_bias:
        fnorm["bias"] = 0.1 * jax.random.normal(ks[2], (D,))
    x = jax.random.normal(ks[3], (B, S, D))
    labels = jax.random.randint(ks[4], (B, S), 0, V).at[0, 5].set(-100)
    mask = None
    if with_mask:
        mask = (jax.random.uniform(ks[5], (B, S)) > 0.2).astype(jnp.int32)
    bias = 0.1 * jax.random.normal(ks[5], (V,)) if with_bias else None
    return head, fnorm, x, labels, mask, bias


def test_no_vocabulary_sized_collective_but_the_heads(devices):
    """fsdp=4, the table sharded as ``choose_pspec`` shards an odd
    vocabulary: value and grad of the loss tail hold ONE collective whose
    operand has a vocabulary-sized dimension for the head (its gather) and
    ONE for its gradient, and no rows, labels or logits cross chips."""
    mesh = build_mesh(fsdp=4, devices=devices[:4])
    V, D, B, S, chunk = 1031, 64, 8, 96, 48   # 4 chunks of 48 rows a chip
    spec = choose_pspec((V, D), mesh)
    assert spec == P(None, "fsdp"), spec      # hidden-sharded, as gpt2-xl's
    model = _model(mesh, True, chunk, V, D)
    sh = lambda *s: NamedSharding(mesh, P(*s))
    args = (jax.ShapeDtypeStruct((V, D), jnp.float32, sharding=sh(*spec)),
            {k: jax.ShapeDtypeStruct((D,), jnp.float32, sharding=sh())
             for k in ("scale", "bias")},
            jax.ShapeDtypeStruct((B, S, D), jnp.float32, sharding=sh("fsdp")),
            jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=sh("fsdp")))
    f = _tail(model, True)
    compiled = jax.jit(
        jax.value_and_grad(lambda t, n, x, l: f(t, n, x, l, None, None),
                           argnums=(0, 1, 2)),
        out_shardings=(sh(), (sh(*spec), {"scale": sh(), "bias": sh()},
                              sh("fsdp")))).lower(*args).compile()
    found = collectives(program_text(compiled))
    assert found, "a sharded loss tail with no collective at all"
    vocab = [c for c in found if any(V in dims for _, dims in c[1])]
    for kind, results, entry in vocab:
        for _, dims in results:
            assert V not in dims or sorted(dims) == sorted((D, V)), (
                f"{kind} moves a vocabulary-sized block that is not the "
                f"head: {dims}")
        assert entry, f"{kind} of the head inside the chunk loop: {results}"
    kinds = sorted(c[0] for c in vocab)
    assert kinds in (["all-gather", "all-reduce"],
                     ["all-gather", "reduce-scatter"]), vocab
    # nothing else may be gathered or exchanged: what is left is sums of
    # scalars and of the final norm's [D] gradients
    for kind, results, _ in found:
        if any(V in dims for _, dims in results):
            continue
        assert kind == "all-reduce", (kind, results)
        assert all(math.prod(dims) <= D for _, dims in results), results


def _case(axes, tied=True, chunk=64, S=96, V=1031, mask=False, z_loss=0.0,
          bias=False):
    return dict(axes=axes, tied=tied, chunk=chunk, S=S, V=V, mask=mask,
                z_loss=z_loss, bias=bias)


_FSDP4 = dict(fsdp=4)
_CASES = {
    "fsdp4-tied-chunked": _case(_FSDP4),
    "fsdp4-tied-dense": _case(_FSDP4, chunk=0),
    "fsdp4-untied-chunked": _case(_FSDP4, tied=False),
    "fsdp4-untied-dense": _case(_FSDP4, tied=False, chunk=0),
    # a divisible vocabulary: choose_pspec shards an untied head on V
    "fsdp4-untied-vocab-sharded": _case(_FSDP4, tied=False, V=1024),
    "fsdp4-tied-vocab-sharded": _case(_FSDP4, V=1024, chunk=0),
    # local token counts the chunk does not divide: 2 x 95, 2 x 191
    "fsdp4-ragged-S95": _case(_FSDP4, S=95),
    "fsdp4-ragged-S191": _case(_FSDP4, tied=False, S=191),
    "fsdp4-mask": _case(_FSDP4, mask=True),
    "fsdp4-mask-dense": _case(_FSDP4, tied=False, chunk=0, mask=True),
    "fsdp4-zloss": _case(_FSDP4, z_loss=1e-3),
    "fsdp4-head-bias": _case(_FSDP4, tied=False, mask=True, z_loss=1e-3,
                             bias=True),
    "fsdp8-chunk-over-local": _case(dict(fsdp=8), chunk=128),
    "dp2xfsdp2": _case(dict(dp=2, fsdp=2), S=95, mask=True),
    # vocab-parallel logits over tp stay; only fsdp is gathered
    "fsdp2xtp2-tied": _case(dict(fsdp=2, tp=2), V=1024),
    "fsdp2xtp2-untied": _case(dict(fsdp=2, tp=2), tied=False, V=1024, S=95,
                              mask=True, z_loss=1e-3, bias=True),
    # the sequence over sp is one more axis of the rows
    "fsdp2xsp2-tied": _case(dict(fsdp=2, sp=2), chunk=32, mask=True,
                            z_loss=1e-3),
    "fsdp2xsp2-untied": _case(dict(fsdp=2, sp=2), tied=False, chunk=32,
                              bias=True),
    "fsdp2xsp2-dense": _case(dict(fsdp=2, sp=2), chunk=0, mask=True),
    "one-device": _case(None, mask=True, z_loss=1e-3),
    "one-device-dense": _case(None, tied=False, chunk=0, S=95, bias=True),
    "data-axes-of-1": _case(dict(fsdp=1), S=95, mask=True),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_sharded_loss_tail_is_the_single_device_one(devices, rng, case):
    """Loss and gradients (hidden states, head, final norm) of the loss
    tail under a mesh against the parent's formula on one device
    (``h[:, :-1]``, ``labels[:, 1:]``, dense)."""
    c = _CASES[case]
    axes, tied, V, D, B, S = c["axes"], c["tied"], c["V"], 64, 8, c["S"]
    set_global_mesh(None)
    mesh = None
    if axes:
        mesh = build_mesh(devices=devices[:math.prod(axes.values())], **axes)
    kw = dict(z_loss=c["z_loss"], lm_head_bias=c["bias"])
    model = _model(mesh, tied, c["chunk"], V, D, **kw)
    inputs = _inputs(rng, tied, B, S, D, V, c["mask"], c["bias"],
                     norm_bias=tied)
    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))
    want, want_g = grad(_parent_formula(model, tied))(*inputs)

    placed = inputs
    if mesh is not None:
        sh = lambda *s: NamedSharding(mesh, P(*s))
        logical = None
        if axes.get("tp", 1) > 1:           # CausalLM.param_specs' split
            logical = P("tp", None) if tied else P(None, "tp")
        head_spec = choose_pspec(inputs[0].shape, mesh, existing=logical)
        assert "fsdp" in tuple(head_spec) or axes["fsdp"] == 1, head_spec
        batch = sh(("dp", "fsdp", "ep"), "sp")
        put = lambda a, s: None if a is None else jax.device_put(a, s)
        placed = (put(inputs[0], sh(*head_spec)),
                  jax.device_put(inputs[1], sh()), put(inputs[2], batch),
                  put(inputs[3], batch), put(inputs[4], batch),
                  put(inputs[5], sh()))
    got, got_g = grad(_tail(model, tied))(*placed)

    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-7)


@pytest.mark.parametrize("axes", [None, dict(fsdp=1)],
                         ids=["no-mesh", "data-axes-of-1"])
def test_one_device_program_holds_no_collective_and_no_logits_copy(
        devices, axes):
    """Without data axes to shard over, every constraint is a no-op: no
    collective, and no transpose or copy of a ``[rows, V]`` block."""
    V, D, B, S, chunk = 1031, 64, 4, 95, 64
    set_global_mesh(None)
    mesh = build_mesh(devices=devices[:1], **axes) if axes else None
    model = _model(mesh, True, chunk, V, D)
    f = _tail(model, True)
    args = (jax.ShapeDtypeStruct((V, D), jnp.float32),
            {k: jax.ShapeDtypeStruct((D,), jnp.float32)
             for k in ("scale", "bias")},
            jax.ShapeDtypeStruct((B, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, S), jnp.int32))
    text = program_text(jax.jit(jax.value_and_grad(
        lambda t, n, x, l: f(t, n, x, l, None, None),
        argnums=(0, 1, 2))).lower(*args).compile())
    assert not collectives(text)
    for line in text.splitlines():
        m = re.search(r"= \S+\[(\d+),(\d+)\]\S* (transpose|copy)\(", line)
        if m:
            assert V not in (int(m.group(1)), int(m.group(2))) or \
                D in (int(m.group(1)), int(m.group(2))), line
