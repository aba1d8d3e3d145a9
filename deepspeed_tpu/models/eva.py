"""EVA attention (EvaByte; Zheng et al., arXiv:2302.04542, in the
deterministic per-head form of the EvaByte release) in plain XLA, and the
arithmetic of its cache layout.

A query at position ``i`` of window ``w = i // W`` attends, in ONE float32
softmax,

- exactly to the keys of its own window so far, ``wW <= j <= i``, and
- to one learned summary per ``C``-token chunk of every EARLIER window
  (``c < w * W/C``; none of its own window), where for the chunk's roped
  keys ``k_j`` and values ``v_j`` and the head's two learned vectors

      ktilde_c = sum_j softmax_j(k_j . mu)  k_j
      vtilde_c = sum_j softmax_j(k_j . phi) v_j.

So a slot's cache is two kinds of state (``serving/paged_kv.py``): ``W``
WINDOW rows that are overwritten in place window after window (position
``p`` lives at row ``p % W``), then SUMMARY rows that gain ``W/C`` rows
each time a window closes (chunk ``c`` lives at row ``W + c``, ``ktilde``
in the K buffer and ``vtilde`` in the V buffer).  Every function here that
takes a ``view`` takes that logical layout, ``[B, H, W + summary rows,
Dh]``; the paged pool is the same rows cut into pages, window pages first.

The Pallas forms of the decode-time pieces (``eva_decode_paged``,
``eva_summarize_paged``) are in ``ops/pallas/decode.py``; these are their
parity targets and the whole of the no-cache and prefill paths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
F32 = jnp.float32


def summary_rows(max_tokens: int, window: int, chunk: int) -> int:
    """Summary rows a slot of ``max_tokens`` positions can come to hold:
    ``W/C`` for every window it can open (the last one's are written and
    never attended, which keeps every write in bounds)."""
    return -(-int(max_tokens) // window) * (window // chunk)


def closed_summary_rows(tokens: int, window: int, chunk: int) -> int:
    """Summary rows that exist once positions ``[0, tokens)`` are written:
    those of the windows that have closed."""
    return (int(tokens) // window) * (window // chunk)


def summarize(k, v, mu, phi, chunk: int):
    """k, v [..., H, S, Dh] (roped keys; ``S`` a multiple of ``chunk``);
    mu, phi [H, Dh] -> (ktilde, vtilde) [..., H, S/chunk, Dh] float32: each
    chunk's keys pooled by softmax(k . mu), its values by softmax(k . phi),
    both softmaxes over the chunk's positions, in float32."""
    *lead, H, S, Dh = k.shape
    kc = k.astype(F32).reshape(*lead, H, S // chunk, chunk, Dh)
    vc = v.astype(F32).reshape(*lead, H, S // chunk, chunk, Dh)
    pool = lambda w: jax.nn.softmax(
        jnp.sum(kc * w.astype(F32)[:, None, None, :], axis=-1), axis=-1)
    return (jnp.sum(pool(mu)[..., None] * kc, axis=-2),
            jnp.sum(pool(phi)[..., None] * vc, axis=-2))


def eva_attention(q, k, v, mu, phi, *, window: int, chunk: int, scale: float):
    """The whole sequence at once, no cache: q, k, v [B, H, S, Dh] (roped)
    -> [B, H, S, Dh] in ``q.dtype``.  One window of queries at a time
    (``lax.map``), so the scores held are [B, H, W, W + S/C]."""
    B, H, S, Dh = q.shape
    W = window
    pad = (-S) % W                 # pad keys lie after every real query
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for t in (q, k, v))
    n_win = (S + pad) // W
    per = W // chunk
    ks, vs = summarize(k, v, mu, phi, chunk)           # [B, H, n_win*per, Dh]
    wins = lambda t: jnp.moveaxis(
        t.astype(F32).reshape(B, H, n_win, W, Dh), 2, 0)
    causal = jnp.tril(jnp.ones((W, W), bool))
    chunk_id = jnp.arange(n_win * per)

    def one(args):
        w, qw, kw, vw = args                           # [B, H, W, Dh]
        s_win = jnp.einsum("bhqd,bhkd->bhqk", qw, kw) * scale
        s_sum = jnp.einsum("bhqd,bhcd->bhqc", qw, ks) * scale
        s = jnp.concatenate(
            [jnp.where(causal, s_win, NEG_INF),
             jnp.where(chunk_id < w * per, s_sum, NEG_INF)], axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("bhqk,bhkd->bhqd", p[..., :W], vw)
                + jnp.einsum("bhqc,bhcd->bhqd", p[..., W:], vs))

    o = jax.lax.map(one, (jnp.arange(n_win), wins(q), wins(k), wins(v)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, S + pad, Dh)[:, :, :S]
    return o.astype(q.dtype)


def cached_attention(q, kview, vview, q_pos, *, window: int, chunk: int,
                     scale: float):
    """q [B, H, s, Dh] at absolute positions ``q_pos`` ([s] shared, or
    [B, s] per row) over the logical views [B, H, W + summary rows, Dh].
    The queries of one row all lie in ONE window, the one whose rows the
    view's window part holds (a prefill chunk never straddles a boundary:
    ``prefill_chunk`` divides ``W``).  Dense masked float32 softmax."""
    q_pos = jnp.asarray(q_pos)
    q_pos = q_pos[None] if q_pos.ndim == 1 else q_pos  # [Bq, s]
    rows = jnp.arange(kview.shape[2])
    n_win = (q_pos % window + 1)[..., None]
    n_sum = ((q_pos // window) * (window // chunk))[..., None]
    ok = jnp.where(rows < window, rows < n_win, rows - window < n_sum)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(F32),
                   kview.astype(F32)) * scale
    p = jax.nn.softmax(jnp.where(ok[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vview.astype(F32)).astype(q.dtype)


def write_window_summaries(kview, vview, mu, phi, win, *, window: int,
                           chunk: int):
    """Pool the views' window rows and write the ``W/C`` summaries at window
    ``win``'s summary rows (``win`` a traced scalar, or [B] per row).  Safe
    to call with the window still open: its own summaries are attended only
    from later windows, and the call that closes it writes them last."""
    ks, vs = summarize(kview[:, :, :window], vview[:, :, :window], mu, phi,
                       chunk)
    per = window // chunk
    win = jnp.asarray(win, jnp.int32)
    if win.ndim == 0:
        at = (0, 0, window + win * per, 0)
        return (jax.lax.dynamic_update_slice(kview, ks.astype(kview.dtype), at),
                jax.lax.dynamic_update_slice(vview, vs.astype(vview.dtype), at))
    b = jnp.arange(kview.shape[0])[:, None]
    r = window + win[:, None] * per + jnp.arange(per)[None]      # [B, per]
    put = lambda buf, t: buf.at[b, :, r, :].set(
        t.transpose(0, 2, 1, 3).astype(buf.dtype))
    return put(kview, ks), put(vview, vs)
