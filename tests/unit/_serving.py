"""What the served models' test modules share: their parameters' noise, and
one ``ServingEngine`` for the cases of a module that differ in their
requests alone: an engine traces, lowers and compiles its chunk programs and
its decode block once, and a case that builds its own pays for them again.

A module holds the engine in a ``scope="module"`` fixture beside its
``model`` and hands it to a case through :func:`as_found`, so that a case
that leaves a request or a page behind fails ITSELF and not the next one.
A case that tests construction, refusal, close, preemption under a small
pool, or that counts the prefix cache's hits, builds an engine of its own.
"""

import contextlib

import jax


def with_noise(params, key=1, keys=64):
    """``params`` with 0.05 of a normal draw on every leaf (a gain of exactly
    1 would hide a dropped norm), leaf ``i`` by key ``i`` of ``keys`` split
    from ``key``.  The draws are ONE jitted program: drawn a leaf at a time
    they were half a model fixture's seconds.  The sum stays outside it, so
    the values are bit for bit what the leaf-by-leaf form gave."""
    leaves, tree = jax.tree.flatten(params)
    draws = jax.jit(lambda ks: [jax.random.normal(k, a.shape)
                                for k, a in zip(ks, leaves)])(
        jax.random.split(jax.random.PRNGKey(key), keys)[:len(leaves)])
    return tree.unflatten([a + 0.05 * n for a, n in zip(leaves, draws)])


def assert_idle(serve, when):
    """Nothing queued, nothing in a slot, no page out of its accounts; the
    finished list handed back (it keeps every request it was given)."""
    sched = serve.scheduler
    assert not sched.has_work, (
        f"{when}: {sched.num_occupied} request(s) in a slot, "
        f"{len(sched._queue)} queued")
    sched.drain_finished()
    assert not any(serve.pool._owned), f"{when}: a free slot owns pages"
    serve.pool.check_no_leak()
    if serve.prefix_cache is not None:
        serve.prefix_cache.check_no_leak()


@contextlib.contextmanager
def as_found(serve):
    """The module's engine for one case: idle when the case gets it (where an
    earlier case's failure left it otherwise, that is said here) and idle
    when the case ends."""
    assert_idle(serve, "as the case found the engine")
    yield serve
    assert_idle(serve, "as the case left the engine")


def tapped_engine(build):
    """The body of a module's ``tapped`` fixture: ``(taps, engine)``, the
    engine ``build()`` makes inside a ``ServeTaps`` block of its own, for
    :func:`read_served`; closed when the module is done.  Its programs carry
    the taps' callbacks whoever serves on it; they add nothing to the
    arithmetic."""
    from benchmarks.lib.serve_taps import ServeTaps

    taps = ServeTaps()
    with taps:
        serve = build()
    yield taps, serve
    serve.close()


def read_served(tapped, prompts, max_new_tokens):
    """``serve_and_read`` on the module's tapped engine, as one case's own:
    the events of earlier cases dropped, the taps on again for a bucket this
    case is the first to use (it is traced here)."""
    from benchmarks.lib.serve_taps import serve_and_read

    taps, serve = tapped
    with taps, as_found(serve):
        taps.events.clear()
        taps.chunks.clear()
        return serve_and_read(taps, serve, prompts, max_new_tokens)
