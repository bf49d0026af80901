"""Declarative tables behind the repro-lint rules — edit HERE, not the rules.

Every load-bearing convention the checker enforces is written down in this
one module as plain data: the layering contract (R4), the jit-root modules
whose call closures must stay host-free (R1), the host-side APIs banned
inside that closure (R1), and the trace-event type names whose
construction must be recorder-guarded (R3).  The rule implementations in
``repro.analysis.rules`` read these tables and nothing else, so promoting
a new invariant to "mechanically checked" is usually a one-line table edit
plus a fixture test — see the "Static analysis" section of ROADMAP.md.

This package must stay importable with nothing but the standard library:
the CI lint job runs before any dependency install, and the linter must be
able to lint a tree whose runtime imports are broken.  That property is
itself encoded below (the ``repro.analysis`` rows of ``LAYERING``).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class LayerRule:
    """One forbidden import edge class, checked by R4.

    ``scope``      dotted module prefix the rule constrains.
    ``forbidden``  dotted prefix scope modules must not import.
    ``transitive`` False: only *direct top-level* imports are checked.
                   True: the import graph is BFS-closed over top-level
                   imports first (function-level imports never count —
                   they are the sanctioned cycle-breaker/lazy-dep idiom).
    ``allow``      prefixes exempt from ``forbidden`` (carve-outs).
    ``why``        one line a failing developer can act on.
    """

    scope: str
    forbidden: str
    transitive: bool = False
    allow: tuple[str, ...] = ()
    why: str = ""


LAYERING: tuple[LayerRule, ...] = (
    # repro.obs is the bottom layer: trace readers (the explain CLI, CI
    # chain checks) must run on machines with no accelerator stack at all.
    LayerRule("repro.obs", "jax", transitive=True,
              why="trace readers must work without jax, even transitively"),
    LayerRule("repro.obs", "repro", allow=("repro.obs",),
              why="obs is the bottom layer: stdlib + numpy only, so every "
                  "other package may import it unconditionally"),
    # repro.core re-exports the control API lazily (function-level); a
    # top-level import would recreate the core <-> control cycle.
    LayerRule("repro.core", "repro.control",
              why="core re-exports control lazily; a top-level import "
                  "recreates the import cycle"),
    LayerRule("repro.core", "repro.cluster",
              why="the metric/scheduler layer consumes views passed in; it "
                  "never reaches into the simulator"),
    # control depends on cluster; the reverse edge exists only at function
    # level (state.py folds the detector/forecaster into its scan carry).
    LayerRule("repro.cluster", "repro.control",
              why="control -> cluster is the real dependency direction; the "
                  "scan-fold imports in state.py stay function-level"),
    # the fleet tables/topology are leaf data consumed by schedulers and
    # the policy alike; reaching upward would make machine-class edits
    # drag the whole mitigation stack into the admission hot path.
    LayerRule("repro.cluster.fleet", "repro.control", transitive=True,
              why="fleet is leaf data (classes, topology, prefilter); it "
                  "must stay importable without the control stack"),
    # kernels are leaf accelerator code: they consume packed arrays and
    # constants from repro.core, never views/policies.
    LayerRule("repro.kernels", "repro.control", transitive=True,
              why="kernels are leaf accelerator code; depending on the "
                  "control stack would drag host policy into every "
                  "kernel trace"),
    # the linter itself: stdlib-only, lintable-while-broken.
    LayerRule("repro.analysis", "repro", allow=("repro.analysis",),
              why="the linter must be able to lint a tree whose runtime "
                  "imports are broken"),
    LayerRule("repro.analysis", "jax", transitive=True,
              why="the CI lint job runs before dependencies install"),
    LayerRule("repro.analysis", "numpy",
              why="the CI lint job runs before dependencies install"),
)


# --------------------------------------------------------------------------
# R1 — jit purity
# --------------------------------------------------------------------------

# Modules whose jax.jit / lax.scan / lax.switch roots seed the R1 call
# closure: the batched rollout core, the detector and forecaster folds, the
# fleet prefilter and the replay's statistics program; add
# a module here when a new jit'd scoring path (e.g. the planned
# multi-objective optimizer) is promoted to load-bearing.
JIT_ROOT_MODULES: tuple[str, ...] = (
    "repro.cluster.experiment",
    "repro.cluster.fleet",
    "repro.cluster.state",
    "repro.control.detector",
    "repro.control.forecast",
)

# Dotted call prefixes that are host-side by definition: calling any of
# these under trace either silently freezes a value at trace time
# (time/random) or breaks tracing outright.
HOST_CALL_PREFIXES: tuple[str, ...] = (
    "time.",
    "random.",
    "np.random.",
    "numpy.random.",
)

# jax entry points that make a wrapped/receiving callable traced code.
JIT_WRAPPERS: tuple[str, ...] = ("jax.jit", "jax.vmap", "jax.pmap", "jit")
TRACED_CALLABLE_TAKERS: tuple[str, ...] = (
    "lax.scan", "lax.switch", "lax.cond", "lax.while_loop", "lax.fori_loop",
    "lax.map", "lax.associative_scan",
)


# --------------------------------------------------------------------------
# R3 — zero-overhead tracing
# --------------------------------------------------------------------------

# Event types defined in repro.obs.events whose construction outside
# repro/obs/ must sit under an `if recorder:`-style truthiness guard.  The
# rule unions this table with the Event subclasses it discovers when
# events.py is part of the linted set, and tests/test_lint.py asserts the
# two agree — so a new event type added without updating this line fails
# the suite, not silently.
OBS_EVENTS_MODULE = "repro.obs.events"
OBS_EVENT_TYPES: tuple[str, ...] = (
    "ActionExecuted",
    "ActionPlanned",
    "ActionVerified",
    "AdmissionDecision",
    "Event",
    "GenericEvent",
    "HotspotFlag",
    "PhaseTimings",
    "RetryDrained",
    "RetryQueued",
    "TrustGateTransition",
)

# Identifiers accepted as "the recorder" in a guard expression: a bare
# name, or the terminal attribute of e.g. ``self._recorder``.
RECORDER_NAMES: tuple[str, ...] = ("rec", "recorder", "_recorder")


# --------------------------------------------------------------------------
# R5 — PRNG key discipline
# --------------------------------------------------------------------------

# jax.random functions that *derive* keys rather than consuming them; any
# other jax.random.* call is treated as a draw that consumes its key.
PRNG_DERIVERS: tuple[str, ...] = (
    "split", "fold_in", "PRNGKey", "key", "key_data", "wrap_key_data",
    "clone",
)
