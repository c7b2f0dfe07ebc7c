"""The per-slot scan decode tick: the oracle for the engine's batched tick.

Before the engine ran one pinned batched body over all ``max_slots``
rows, its decode tick scanned ONE barrier-pinned batch-1 decode body over
the slot axis, then kept free and PREFILLING rows' old bits with an
exact select. That tick reads every weight once per slot, so it is no
longer a serving path; it stays here, for the dense layout, as the
reference the batched tick is compared against
(``tests/test_serve_engine.py``) and as a hand-built per-slot scan for
the trace rule that forbids one (``tests/test_analysis_trace.py``).
:func:`tick_logits` reads the logits the engine's own tick computes,
which the tick does not return.
"""

import jax
import jax.numpy as jnp

from repro.kernels.schemes import use_policy
from repro.models.layers import activation_sq_norm
from repro.serve.slots import select_live


def scan_tick(engine):
    """A jitted ``tick(params, cache, tokens, pos, seeds, eidx, temps,
    live) -> (new_cache, next_tok, norms, logits)`` over a dense
    engine's slot cache, computed by the per-slot scan. Sampling keys
    and the telemetry norm follow the engine's own definitions."""
    model, ec, policy = engine.model, engine.ec, engine.policy
    axes = engine.slots.batch_axes
    vocab = engine.cfg.vocab_size
    base_key = jax.random.key(ec.sample_seed)

    def decode_one(params, row, token, pos, seed, eidx, temp):
        row, token, pos, seed, eidx, temp = jax.lax.optimization_barrier(
            (row, token, pos, seed, eidx, temp))
        cache1 = jax.tree.map(jnp.expand_dims, row, axes)
        logits, new = model.decode_step(params, cache1, token[None], pos)
        new_row = jax.tree.map(jnp.squeeze, new, axes)
        key = jax.random.fold_in(jax.random.fold_in(base_key, seed), eidx)
        greedy = jnp.argmax(logits[0]).astype(jnp.int32)
        safe_t = jnp.where(temp > 0, temp, jnp.float32(1.0))
        samp = jax.random.categorical(
            key, logits[0].astype(jnp.float32) / safe_t).astype(jnp.int32)
        tok = jnp.where(temp > 0, samp, greedy)
        return jax.lax.optimization_barrier((logits[0], new_row, tok))

    @jax.jit
    def tick(params, cache, tokens, pos, seeds, eidx, temps, live):
        with use_policy(policy):
            front = jax.tree.map(lambda x, a: jnp.moveaxis(x, a, 0),
                                 cache, axes)

            def body(_, xs):
                return None, decode_one(params, *xs)

            _, (logits, new_front, toks) = jax.lax.scan(
                body, None, (front, tokens, pos, seeds, eidx, temps))
            new = jax.tree.map(lambda x, a: jnp.moveaxis(x, 0, a),
                               new_front, axes)
            new = jax.tree.map(lambda n, o, a: select_live(n, o, a, live),
                               new, cache, axes)
            norms = activation_sq_norm(logits[:, :vocab], scheme=policy)
        return new, toks, norms, logits

    return tick


def tick_logits(tick, *args):
    """The ``[max_slots, V_pad]`` logits of a dense engine tick, read at
    the exit pin of its batched decode body: the tick's own jaxpr, cut
    at that ``optimization_barrier`` and evaluated on ``args``."""
    (call,) = jax.make_jaxpr(tick)(*args).jaxpr.eqns   # the jitted tick
    inner = call.params["jaxpr"]
    n = args[2].shape[0]
    pins = [e for e in inner.jaxpr.eqns
            if e.primitive.name == "optimization_barrier"
            and len(e.outvars[0].aval.shape) == 2
            and e.outvars[0].aval.shape[0] == n]
    assert len(pins) == 1, f"{len(pins)} exit pins carry [{n}, V] logits"
    cut = inner.jaxpr.replace(outvars=[pins[0].outvars[0]])
    (logits,) = jax.core.eval_jaxpr(cut, inner.consts,
                                    *jax.tree.leaves(args))
    return logits
