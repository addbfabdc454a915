"""GPipe-style pipeline parallelism over a `pipe` mesh axis.

Complements the DP/FSDP/TP/EP/SP axes used by the dry-run matrix: stages hold
disjoint layer groups; microbatches stream through with jax.lax collectives
(ppermute) moving activations stage-to-stage inside one jitted step.  The
schedule is the standard fill-run-drain loop: with M microbatches and P
stages the bubble fraction is (P-1)/(M+P-1).

Used by tests/test_distributed.py on host devices; at pod scale the `pipe`
axis would be carved from `model` (DESIGN.md Sec. 5).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(layer_fn, stage_params, x_microbatches, *, mesh, axis="pipe"):
    """Run microbatches through P pipeline stages.

    layer_fn(params, x) -> x applies ONE stage's layer group.
    stage_params: params with leading stage axis [P, ...] (sharded over `pipe`).
    x_microbatches: [M, mb, ...] microbatched inputs (replicated).
    Returns [M, mb, ...] outputs (from the last stage, replicated).
    """
    n_stages = mesh.shape[axis]
    M = x_microbatches.shape[0]
    steps = M + n_stages - 1

    def stage_body(params, xs):
        """Runs on every device of the pipe axis with its own stage params."""
        params = jax.tree.map(lambda t: t[0], params)  # local stage slice
        idx = jax.lax.axis_index(axis)
        state = jnp.zeros_like(xs[0])  # activation currently held by the stage
        outs = jnp.zeros_like(xs)

        def step(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (when in range)
            feed = xs[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(idx == 0, feed, state)
            out = layer_fn(params, inp)
            # last stage emits microbatch t - (P-1)
            emit_t = t - (n_stages - 1)
            emit = jnp.logical_and(idx == n_stages - 1, emit_t >= 0)
            outs = outs.at[jnp.clip(emit_t, 0, M - 1)].set(
                jnp.where(emit, out, outs[jnp.clip(emit_t, 0, M - 1)]))
            # shift activations to the next stage
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs), None

        (state, outs), _ = jax.lax.scan(step, (state, outs), jnp.arange(steps))
        # broadcast the last stage's buffer to every stage (replicated output)
        outs_all = jax.lax.all_gather(outs, axis)  # [P, M, mb, ...]
        return outs_all[n_stages - 1]

    f = jax.shard_map(stage_body, mesh=mesh,
                      in_specs=(P(axis), P()), out_specs=P(),
                      check_vma=False)
    return f(stage_params, x_microbatches)


def sequential_apply(layer_fn, stage_params, x_microbatches):
    """Reference: the same computation without pipelining."""
    def run_one(x):
        def body(x, p):
            return layer_fn(p, x), None
        x, _ = jax.lax.scan(body, x, stage_params)
        return x

    return jax.vmap(run_one)(x_microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
