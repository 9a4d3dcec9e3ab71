"""The skylint rule set: repo invariants the AST layer enforces.

Each rule encodes one discipline the paper's dispatch/communication
analysis depends on. The checks themselves live in `repro.analysis.lint`;
this module is the single place describing WHAT each rule means, its
fix-hint, and where it applies — the README renders from the same
metadata.

Suppression: append ``# skylint: disable=R1`` (comma-separate several
ids) to the offending line, or put it on a comment-only line directly
above. Suppressions should carry a justification comment; the gate
reports them as suppressed, not as clean.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Rule", "RULES", "HOT_PATHS", "KERNEL_INTERNALS",
           "KERNEL_SUBMODULES", "R2_SCOPES", "R6_SCOPES",
           "STATE_OPERANDS", "COMPAT_MODULE"]


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    title: str
    rationale: str
    hint: str


RULES = {
    "R1": Rule(
        "R1", "no host syncs in jitted-reachable code",
        "A `.item()` / `int()/float()/bool()`-on-array / `np.asarray` / "
        "`.block_until_ready()` inside code reachable from a jitted "
        "entry point forces a device round-trip per dispatch — exactly "
        "the per-feed sync the fused streaming path exists to avoid.",
        "move the value into the jitted program (traced data), or hoist "
        "the read out of the hot path and defer it behind the dispatch "
        "(see SkylineStream._maybe_resolve: poll is_ready() and overlay "
        "the pending record in-program until the device delivers); if "
        "the sync is a considered cost, suppress with a justification "
        "comment."),
    "R2": Rule(
        "R2", "no eager per-item shaping in pack paths",
        "Padding or device_put-ing items one at a time inside a Python "
        "loop dispatches O(items) tiny programs and defeats the "
        "two-level bucketed pack (one dispatch per size bucket).",
        "route ragged items through the engine's bucketed pack "
        "(SkylineEngine._pack) — pad host-side into the bucket, ship "
        "once."),
    "R3": Rule(
        "R3", "kernel internals only via the backend registry",
        "Importing repro.kernels.sfs.* / repro.kernels.dominance.* "
        "internals directly pins a call site to one implementation; "
        "the backend registry (resolve_spec) is what lets 'auto' pick "
        "Pallas on TPU and the jnp reference elsewhere — and what new "
        "backends plug into.",
        "import resolve_spec / KernelSpec from repro.kernels.backend "
        "and call through the spec."),
    "R4": Rule(
        "R4", "shard_map/Mesh imports only through repro.compat",
        "repro/compat.py is the one import point for shard_map and "
        "the mesh-construction API, so the repo's sharding conventions "
        "(Auto axis types, check_vma off) live in one place. A raw "
        "import elsewhere bypasses them.",
        "from repro.compat import shard_map, make_mesh, set_mesh."),
    "R5": Rule(
        "R5", "no Python branching on traced values in core/ hot paths",
        "`if`/`while` on a traced scalar either fails to trace or — via "
        "a silent concretization — forces a host sync inside the fused "
        "program, serializing the pipeline the paper's cost model "
        "assumes is one dispatch.",
        "use jnp.where / jax.lax.cond / jax.lax.select on the traced "
        "value, or hoist the decision to a static (Python-int) "
        "configuration value."),
    "R6": Rule(
        "R6", "state-update factories must declare buffer donation",
        "A jitted factory whose program consumes a `state`/`leaves` "
        "operand (the repo's single-owner state-update convention) "
        "without `donate_argnums` compiles to an A/B copy: every "
        "dispatch holds input AND output buffers live, doubling the "
        "fleet's steady-state device bytes — the regression the "
        "Layer-2 HLO aliasing invariant exists to catch.",
        "return `jax.jit(run, donate_argnums=(0,)) if cfg.donate else "
        "jax.jit(run)` (or take a `donate` cache-key parameter); if "
        "the factory is a read-only overlay whose state operand is "
        "legitimately shared (finalize/snapshot views), suppress with "
        "a rationale comment — the suppression documents the "
        "ownership contract."),
}

# R1's second scope: serving-path methods that are NOT jit-reachable
# (they run host-side) but sit on the per-feed critical path, where a
# blocking device read serializes the dispatch pipeline all the same.
# NOT listed (the sanctioned blocking settles, never on a serving op's
# path): SkylineStream._force_resolve / drain — shutdown/test sync
# points only. `_wave_feed` stays in scope with no carve-out: a
# repeated overflow of a slot with a pending record in flight *chains*
# onto the live record list (every wave overlays all alive records
# in-program), so no serving code path retains a sanctioned blocking
# read.
HOT_PATHS = {
    "repro.serve.engine": {
        "SkylineStream.feed", "SkylineStream.tick",
        "SkylineStream.expire_epoch", "SkylineStream._promote",
        "SkylineStream.snapshot", "SkylineStream._maybe_resolve",
        "_wave_feed",
        "SkylineEngine.run", "SkylineEngine._run_stacked",
        "SkylineEngine.submit", "SkylineEngine.submit_many",
        "SkylineEngine.member_masks",
    },
    "repro.serve.loop": {
        "ServeLoop.submit", "ServeLoop.feed", "ServeLoop._stage_once",
        "ServeLoop._stage_loop", "ServeLoop._admit_locked",
    },
}

# R3: these packages' SUBMODULES are internal; their package __init__
# re-exports the sanctioned dispatcher entry points (which route through
# resolve_spec), so only submodule imports are violations — and only
# outside the kernels package itself.
KERNEL_INTERNALS = ("repro.kernels.sfs", "repro.kernels.dominance")
KERNEL_SUBMODULES = ("kernel", "ops", "ref")

# R2 applies where ragged request data is shaped for dispatch; model /
# checkpoint code legitimately pads in static per-layer loops.
R2_SCOPES = ("serve", "core", "data", "launch")

# R6 applies where the streaming/serving state-update factories live;
# train/checkpoint code manages its own (already donated) step states.
R6_SCOPES = ("core", "serve")
# first-parameter names marking a jitted inner function as a
# state-update program (the operand the single-owner protocol donates):
# `state` for SkylineState / WindowedSkylineState programs, `leaves`
# for slab-arena programs fed from SlabArena.leaves().
STATE_OPERANDS = ("state", "leaves")

# R4: the one module allowed to touch raw shard_map / mesh APIs.
COMPAT_MODULE = "repro.compat"
