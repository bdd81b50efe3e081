"""The enactor: Gunrock's iterative-convergent BSP loop driver (paper §3).

A Gunrock program is a `Problem` (algorithm state pytree), a set of functors,
and an `Enactor` that runs bulk-synchronous operator steps until convergence
(typically: empty frontier, or max-iteration / volatile-flag criteria).

`run_until` wraps `jax.lax.while_loop` with an iteration guard so every
primitive shares the same convergence contract and can be jitted end-to-end
(one XLA program per primitive — the whole-primitive analogue of the paper's
kernel-fusion philosophy).

`run_until_any` is the batched variant: state carries a leading batch axis
(one lane per concurrent traversal — the frontier-matrix view of
GraphBLAST's multi-source BFS), `cond` returns a per-lane flag, and the
loop runs while *any* lane is active. Converged lanes are frozen: the body
still computes them (BSP lockstep — static shapes rule out early exit) but
the driver discards their updates, so stragglers finish while finished
lanes are bit-stable no-ops. Per-lane iteration counts come back alongside
the wall-clock iteration count.

`tiered_step` is the frontier-proportional escape hatch from worst-case
static shapes: one BSP step dispatched over a static capacity ladder
(`lax.switch`), so the edge-shaped intermediates inside the step are
sized to the live workload's tier instead of the graph. Only state —
frontier/vertex-shaped, tier-independent — crosses the switch boundary,
which is what makes every rung bit-identical given enough capacity.

Telemetry (`obs.telemetry`): both loops accept an optional read-only
``probe`` — ``probe(prev_state, new_state) -> {column: value}`` —
recorded into a caller-provided ``TelemetryBuffer`` carried alongside
the loop state. ``probe=None`` is byte-for-byte the historical path;
with a probe the loop returns the filled buffer as one extra element.
Probes observe, never steer: nothing they compute feeds back into the
step, which is what makes the telemetry on/off bit-parity contract
(tests/test_obs.py) hold by construction.

Scope names (DESIGN.md §10): every device op a primitive compiles
carries an ``op_name`` under one of three roots — ``enactor.*`` for the
loop machinery here (``enactor.loop``, ``enactor.select_lanes``,
``enactor.tier``, ``enactor.telemetry``) and the primitives' direction
choice (``enactor.direction``), ``op.*`` for operators and their apply,
``primitive.*`` for set-up and the result. ``tiered_step`` tags each rung
``tier_<cap>``. The scopes change HLO metadata only.
"""
from __future__ import annotations

from typing import Callable, Sequence, TypeVar

import jax
import jax.numpy as jnp

S = TypeVar("S")


@jax.named_scope("enactor.loop")
def run_until(cond: Callable[[S], jax.Array],
              body: Callable[[S], S],
              state: S,
              max_iter: int,
              probe: Callable[[S, S], dict] | None = None,
              telemetry=None,
              budget=None):
    """while (cond(state) && it < max_iter): state = body(state).

    Returns (final_state, iterations_run). ``max_iter`` bounds the loop so
    XLA sees a well-founded while; primitives pass n (or a diameter bound).

    ``budget`` (a ``repro.ft.Budget``, duck-typed via ``cap_iters`` to keep
    the core free of an ft import) clamps ``max_iter`` to the query's
    iteration budget: the loop then returns the *partial* state at the cap
    — callers compare ``iters`` against their convergence predicate to
    stamp ``converged`` / ``deadline_exceeded`` flags. ``budget=None`` is
    byte-for-byte the historical path. Wall-clock budgets are enforced
    host-side by the serving loop, not here — a jitted while cannot
    consult the host clock.

    With ``probe``/``telemetry`` set, each step additionally records
    ``probe(prev, new)`` into the ``TelemetryBuffer`` and the loop
    returns (final_state, iterations_run, filled_buffer).
    """
    if budget is not None:
        max_iter = budget.cap_iters(max_iter)

    if probe is None:

        def _cond(carry):
            state, it = carry
            return jnp.logical_and(cond(state), it < max_iter)

        def _body(carry):
            state, it = carry
            return body(state), it + 1

        (final, iters) = jax.lax.while_loop(_cond, _body,
                                            (state, jnp.int32(0)))
        return final, iters

    if telemetry is None:
        raise ValueError("probe= requires a telemetry buffer")

    def _cond_t(carry):
        state, it, _ = carry
        return jnp.logical_and(cond(state), it < max_iter)

    def _body_t(carry):
        state, it, buf = carry
        new = body(state)
        with jax.named_scope("enactor.telemetry"):
            buf = buf.record(**probe(state, new))
        return new, it + 1, buf

    final, iters, buf = jax.lax.while_loop(
        _cond_t, _body_t, (state, jnp.int32(0), telemetry))
    return final, iters, buf


@jax.named_scope("enactor.select_lanes")
def select_lanes(mask: jax.Array, on_true: S, on_false: S) -> S:
    """Per-lane pytree select: ``mask`` (B,) broadcast against every
    leaf's leading batch axis. The one place the batched engine's
    lane-choice contract lives (freezing, mixed-direction picks, relax
    vs bucket-pop)."""

    def pick(a, c):
        m = mask.reshape(mask.shape + (1,) * (a.ndim - 1))
        return jnp.where(m, a, c)

    return jax.tree_util.tree_map(pick, on_true, on_false)


@jax.named_scope("enactor.loop")
def run_until_any(cond: Callable[[S], jax.Array],
                  body: Callable[[S], S],
                  state: S,
                  max_iter: int,
                  probe: Callable[[S, S], dict] | None = None,
                  telemetry=None,
                  budget=None):
    """Batched BSP loop: iterate while any lane of ``cond(state)`` holds.

    Contract:
      * every leaf of ``state`` has a leading batch axis of size B;
      * ``cond(state)`` returns a (B,) bool of still-active lanes;
      * ``body(state)`` computes one step for ALL lanes (lockstep).

    The driver masks the update per lane: a lane whose ``cond`` was False
    entering the step keeps its old state bit-for-bit (frozen), so a
    converged traversal is a no-op while ragged stragglers continue.
    Returns (final_state, per_lane_iters (B,) int32, iterations_run ()).

    With ``probe``/``telemetry`` set, each wall-clock step records
    ``probe(prev, new)`` (``new`` is the already lane-masked state, so
    frozen lanes report their frozen values) and the filled buffer comes
    back as a fourth element; per-lane valid lengths are exactly the
    returned ``lane_iters``.

    ``budget`` clamps ``max_iter`` exactly as in :func:`run_until`; lanes
    still active at the cap come back partial, and ``cond(final)`` tells
    the caller which lanes those are.
    """
    if budget is not None:
        max_iter = budget.cap_iters(max_iter)

    # the (B,) active mask rides in the carry so cond runs once per step
    if probe is None:

        def _cond(carry):
            _, _, it, active = carry
            return jnp.logical_and(jnp.any(active), it < max_iter)

        def _body(carry):
            st, lane_iters, it, active = carry
            st = select_lanes(active, body(st), st)  # freeze finished lanes
            return (st, lane_iters + active.astype(jnp.int32), it + 1,
                    cond(st))

        active0 = cond(state)
        lanes0 = jnp.zeros(active0.shape, jnp.int32)
        final, lane_iters, iters, _ = jax.lax.while_loop(
            _cond, _body, (state, lanes0, jnp.int32(0), active0))
        return final, lane_iters, iters

    if telemetry is None:
        raise ValueError("probe= requires a telemetry buffer")

    def _cond_t(carry):
        _, _, it, active, _ = carry
        return jnp.logical_and(jnp.any(active), it < max_iter)

    def _body_t(carry):
        st, lane_iters, it, active, buf = carry
        new = select_lanes(active, body(st), st)
        with jax.named_scope("enactor.telemetry"):
            buf = buf.record(**probe(st, new))
        return (new, lane_iters + active.astype(jnp.int32), it + 1,
                cond(new), buf)

    active0 = cond(state)
    lanes0 = jnp.zeros(active0.shape, jnp.int32)
    final, lane_iters, iters, _, buf = jax.lax.while_loop(
        _cond_t, _body_t,
        (state, lanes0, jnp.int32(0), active0, telemetry))
    return final, lane_iters, iters, buf


def tiered_step(need, caps: Sequence[int],
                step_of: Callable[[int], Callable[[S], S]],
                state: S, with_index: bool = False):
    """Run one BSP step at the smallest capacity tier holding ``need``.

    ``caps`` is the static power-of-two ladder (``backend.tier_plan``),
    ``need`` the traced workload upper bound (e.g. the frontier's degree
    sum), ``step_of(cap)`` builds the step function for one static tier
    capacity. Every branch must return state of identical structure —
    which holds by construction when only frontier/vertex-shaped state
    crosses the boundary and the tier sizes just the edge-shaped
    intermediates. A single-rung ladder skips the switch entirely (the
    untiered / pinned case — also the contract of every distributed
    placement, sharded and 2d alike, where per-device tier choices
    would desynchronize collective shapes).

    Each rung runs under a ``tier_<cap>`` scope and the rung choice under
    ``enactor.tier``, so a trace attributes time per rung.

    ``with_index=True`` additionally returns the chosen tier index as a
    traced int32 — the telemetry hook for "which rung fired this step"
    without the caller recomputing the ladder search.
    """
    def rung(cap):
        return jax.named_scope(f"tier_{cap}")(step_of(cap))

    if len(caps) == 1:
        if with_index:
            return rung(caps[0])(state), jnp.int32(0)
        return rung(caps[0])(state)
    from .frontier import tier_index
    with jax.named_scope("enactor.tier"):
        idx = tier_index(need, tuple(caps))
        out = jax.lax.switch(idx, [rung(c) for c in caps], state)
    if with_index:
        return out, idx
    return out
