"""In-graph multi-step chaining shared by the model train-step factories.

``lax.scan`` of K optimizer steps inside one compiled program: a single
dispatch covers the whole chain, taking host→device launch latency off
the critical path. Factories
wrap the returned chain in their own ``jax.jit`` so each keeps its public
signature (incl. keyword ``step_idx``) and donation contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def multi_step(one_step, n_carry: int, scan_steps: int,
               indexed: bool = False):
    """Chain ``one_step`` into ``scan_steps`` sequential optimizer steps.

    ``one_step(*carry, *consts[, step_idx]) -> (*carry, *outs)`` where the
    first ``n_carry`` positional args (and results) are the training state
    threaded through the chain and the rest are loop-invariant inputs.
    Returns a function of the same positional signature yielding the final
    carry plus the LAST step's outs.

    ``indexed=True`` treats the final argument as a step index: scanned
    step ``i`` receives ``step_idx * scan_steps + i``, so per-step dropout
    keys stay fresh across both the chain and successive dispatches.

    SAME-BATCH semantics: the non-carry inputs (the batch) are
    loop-invariant — every scanned step consumes the SAME batch, so
    ``scan_steps > 1`` means K optimizer steps on one batch per
    dispatch. That is the right construct for throughput benchmarking
    (device-rate measurement with dispatch latency off the critical
    path) and deliberate multi-epoch-per-batch training; it is NOT
    multi-batch training — a training loop that wants a fresh batch per
    optimizer step must keep ``scan_steps=1`` (or restructure the batch
    as a scanned ``[K, ...]`` input itself).

    ``scan_steps <= 1`` returns ``one_step`` behavior unchanged (guarding
    0/negative values: a zero-length scan would run no steps at all).
    """
    if scan_steps <= 1:
        return one_step

    def chained(*args):
        carry0 = args[:n_carry]
        consts = args[n_carry:]
        if indexed:
            *consts, step_idx = consts

        def body(carry, i):
            if indexed:
                res = one_step(*carry, *consts,
                               step_idx * scan_steps + i)
            else:
                res = one_step(*carry, *consts)
            return res[:n_carry], res[n_carry:]

        carry, outs = jax.lax.scan(
            body, carry0,
            jnp.arange(scan_steps) if indexed else None,
            length=None if indexed else scan_steps)
        return (*carry, *jax.tree_util.tree_map(lambda x: x[-1], outs))

    return chained
