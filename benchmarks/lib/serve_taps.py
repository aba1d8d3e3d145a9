"""Read the logits and the routers' choices out of a ``ServingEngine`` that
serves real requests: the engine's own chunk programs and paged decode
blocks, untouched, with ordered ``jax.debug.callback`` taps wrapped around
four functions they call.

The engine samples on the device and returns tokens only, so agreement of
LOGITS with the plain reference (the ``model-configs`` guide, section 3)
needs the values copied out on the way.  The taps add host callbacks to the
programs and nothing to their arithmetic; they belong to the unit test and
to ``tools/olmoe_agreement.py`` and are never on in a timed run.

The event stream, in program order:

    chunk program   ("chunk",) ("route", idx [cb, k]) x layers
                    ("sampled", logits [1, V] at the chunk's last token)
    decode step     ("step", pos [B], live [B]) ("route", idx [B, k]) x layers
                    ("logits", the live rows of [B, V])

and ``ServingEngine._prefill_one_chunk`` is wrapped on the host, so that the
i-th chunk program is known to be request r's tokens [off, off + c).
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


class ServeTaps:
    """Context manager: taps on, events in ``self.events``, chunk owners in
    ``self.chunks`` (request, offset, tokens), in dispatch order."""

    def __init__(self):
        self.events: List[tuple] = []
        self.chunks: List[tuple] = []
        self._undo = []

    def _record(self, tag):
        def record(*arrays):
            arrays = [np.array(a) for a in arrays]
            if tag == "step":
                self._live = np.flatnonzero(arrays[1])
            elif tag == "logits":           # [B, V]: keep the live rows
                arrays = [arrays[0][self._live]]
            self.events.append((tag, *arrays))
        return record

    def _patch(self, obj, name, new):
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, new)

    def __enter__(self):
        import jax

        from deepspeed_tpu.models import fused_decode
        from deepspeed_tpu.moe import sharded_moe
        from deepspeed_tpu.serving import engine as serving

        tap = lambda tag, *arrays: jax.debug.callback(
            self._record(tag), *arrays, ordered=True)
        topk, forward = sharded_moe.topk_weights, serving.forward_with_cache
        sample, step = serving.sample_token, fused_decode.decode_step
        owner = serving.ServingEngine._prefill_one_chunk

        def topk_weights(gates, k, normalize=True):
            weight, idx = topk(gates, k, normalize)
            tap("route", idx)
            return weight, idx

        def forward_with_cache(*args, **kwargs):
            tap("chunk")
            return forward(*args, **kwargs)

        def sample_token(logits, *args, **kwargs):
            if logits.shape[0] == 1:        # a chunk program's one row
                tap("sampled", logits)
            return sample(logits, *args, **kwargs)

        def decode_step(cfg, dparams, tokens, cache, pos, **kwargs):
            tap("step", pos, kwargs["moe_live"])
            out = step(cfg, dparams, tokens, cache, pos, **kwargs)
            tap("logits", out[0])
            return out

        def _prefill_one_chunk(engine, req):
            before = req.prefill_pos
            owner(engine, req)
            if req.prefill_pos > before:
                self.chunks.append((req, before, req.prefill_pos - before))

        self._patch(sharded_moe, "topk_weights", topk_weights)
        self._patch(serving, "forward_with_cache", forward_with_cache)
        self._patch(serving, "sample_token", sample_token)
        self._patch(fused_decode, "decode_step", decode_step)
        self._patch(serving.ServingEngine, "_prefill_one_chunk",
                    _prefill_one_chunk)
        return self

    def __exit__(self, *exc):
        for obj, name, old in reversed(self._undo):
            setattr(obj, name, old)
        self._undo.clear()


def serve_and_read(taps: ServeTaps, serve, prompts, max_new_tokens
                   ) -> List[Dict[str, Any]]:
    """Serve ``prompts`` (all submitted at once: at most ``num_slots`` of
    them, so that a slot has one owner) to their ``max_new_tokens`` on an
    engine built INSIDE a :class:`ServeTaps` block.  Returns per request:
    ``tokens`` (the served output), ``logits`` [n_out, V] (the program's, at
    every generated position) and ``routing`` [L, prompt + n_out - 1, k]
    (each layer's expert indices at every position the program computed; the
    empty list for a dense model)."""
    import jax

    assert len(prompts) <= serve.num_slots and serve.num_slots > 1
    reqs = [serve.submit(np.asarray(p, np.int32), max_new_tokens=int(n))
            for p, n in zip(prompts, max_new_tokens)]
    serve.run()
    jax.effects_barrier()
    out = [{"tokens": list(r.output_tokens), "logits": {}, "routing": {}}
           for r in reqs]
    by_slot = {r.slot: i for i, r in enumerate(reqs)}
    by_id = {id(r): i for i, r in enumerate(reqs)}
    chunks = iter(taps.chunks)
    routes: List[np.ndarray] = []
    where = None
    for ev in taps.events:
        if ev[0] in ("chunk", "step"):
            routes, where = [], ev
        elif ev[0] == "route":
            routes.append(ev[1])
        elif ev[0] == "sampled":
            req, off, c = next(chunks)
            rec = out[by_id[id(req)]]
            for j in range(c):
                rec["routing"][off + j] = [r[j] for r in routes]
            if off + c == req.prompt_len:
                rec["logits"][0] = ev[1][0]
        else:                                   # ("logits", live rows)
            _, pos, live = where
            for row, b in zip(ev[1], np.flatnonzero(live)):
                i = by_slot[int(b)]
                p = int(pos[b])
                out[i]["routing"][p] = [r[b] for r in routes]
                out[i]["logits"][p - reqs[i].prompt_len + 1] = row
    for rec, req in zip(out, reqs):
        n = len(rec["tokens"])
        assert sorted(rec["logits"]) == list(range(n)), "a position is missing"
        rec["logits"] = np.stack([rec["logits"][i] for i in range(n)])
        seq = req.prompt_len + n - 1
        assert sorted(rec["routing"]) == list(range(seq))
        layers = len(rec["routing"][0])
        rec["routing"] = [np.stack([rec["routing"][p][l] for p in range(seq)])
                          for l in range(layers)]
    return out
