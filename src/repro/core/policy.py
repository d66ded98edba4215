"""Cost-driven engine policy: the single engine-selection entry point.

The paper's own methodology is an analytic cost model driving design-point
selection (gate counts -> area/power -> pick the dendrite); the TNN design
framework line (Vellaisamy & Shen 2022) closes the same loop for whole
sensory-processing units. This module applies that loop to the *software*
engines: instead of the hand-tuned ``DENSITY_EVENT_MAX`` threshold, an
:class:`EnginePolicy` predicts the runtime of each candidate engine from an
analytic work model calibrated against the committed full-size sweeps
(``benchmarks/artifacts/BENCH_sparsity.json`` and ``BENCH_pipeline.json``)
and picks the cheapest — for both the engine and the compaction bucket
width (DESIGN.md §3.7).

Work model (per volley x neuron pair, int32 ops):

  * dense engines (``closed_form``, ``scan``, ``pallas``) touch every tick
    of every line: work ``= T * n`` -> ``t = c_engine * pairs * T * n``.
  * sparse engines (``event``, ``pallas_compact``) sort the ``m = 2*s``
    ramp breakpoints of the ``s`` compacted lines and never see ``T``:
    ``t = pairs * (a_event + b_event * m)``. The ``s log s`` sort factor is
    absorbed into the affine slope over the bucket ladder's range (m <=
    2*LANE_WIDTH), where the fit error stays under the decision margin.

Calibration against the committed artifacts (B=Q=n=T=64, pairs=4096):
``c_closed_form`` is the median closed-form row over the six densities;
``a_event``/``b_event`` are the least-squares fit over the compacted and
uncompacted event rows (the bench places exactly ``round(density*n)``
spiking lines per volley, so each row's bucket width — and hence ``m`` —
is known); ``c_scan`` transfers the pipeline sweep's scan/closed-form
ratio (1.45x at depth 1) onto ``c_closed_form``. :func:`fit_coefficients`
re-derives the fit from an artifact's result rows so the property suite
can assert the committed defaults and a fresh fit pick the same engine on
every committed cell (tests/test_policy.py).

Resolution semantics: an explicit backend name runs as named or raises
(a Pallas engine under a mesh its column stack cannot tile is an error,
never a silent switch); the fused Pallas kernel preempts ``auto`` on TPU;
only ``auto`` degrades a Pallas pick to its bit-exact jnp class under such
a mesh; and an unknown workload (tracing: no density, no shape) keeps the
dense choice. The cost model replaces only the
event-vs-closed-form boundary — and, it turns out, moves it: on the
committed sweep the event engine still wins at density 0.5 (59 ms vs 72
ms), which the 0.25 threshold got wrong.

The legacy helpers (``neuron.resolve_backend``, ``neuron.effective_engine``,
``neuron.pallas_shardable``) are deprecated wrappers over this module
(DESIGN.md §6.3); repro-lint RPR009 keeps new callers off them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Iterable, Literal, NamedTuple, Optional, Union

import jax

from repro.core import compaction
from repro.sharding import compat
from repro.sharding import specs as sharding_specs

Backend = Literal["auto", "scan", "closed_form", "event", "pallas",
                  "pallas_compact"]

PolicyMode = Literal["cost", "density"]

#: Legacy ``auto`` threshold (the ``mode="density"`` escape hatch): off-TPU,
#: a measured input density at or below this picks the event engine. The
#: cost mode replaces this constant with the calibrated work model.
DENSITY_EVENT_MAX = 0.25

#: Engines that evaluate over spike-compacted volleys and therefore take a
#: compaction width (``n_active_max``).
SPARSE_ENGINES = ("event", "pallas_compact")

ColumnCounts = Union[int, Iterable[int], None]


def mesh_active() -> bool:
    """Whether an ambient device mesh is entered (compat.set_mesh).

    Under an active mesh engine selection runs the per-kernel capability
    check (:func:`_pallas_shardable`): Pallas engines whose column stack
    tiles the mesh's ``column`` axis run through the shard_map wrappers
    (:mod:`repro.kernels.rnl_shard`); the rest degrade to the bit-exact
    jnp engines, which are sharding-transparent and keep the layout the
    layer constraints pin (DESIGN.md §6.4).
    """
    return bool(compat.get_abstract_mesh().axis_names)


def _pallas_shardable(n_columns: Optional[int]) -> bool:
    """Per-kernel mesh capability of the Pallas engines (DESIGN.md §6.4).

    True when no mesh is active (plain single-device launch). Under a
    mesh, the shard_map fast path needs a 3-D column stack whose column
    count tiles the mesh's ``column`` axis:

      * ``n_columns is None`` (a 2-D ``(B, n)`` bank, no column axis to
        shard over) -> False;
      * mesh without a ``column`` axis -> False (nothing to map over);
      * otherwise ``n_columns %% column-axis-size == 0``.

    When this returns False, ``auto`` degrades to the jnp engines
    (:func:`_effective_engine`) and an explicit Pallas request raises.
    """
    if not mesh_active():
        return True
    if n_columns is None:
        return False
    if sharding_specs.TNN_COLUMN_AXIS not in \
            compat.get_abstract_mesh().axis_names:
        return False
    return n_columns % sharding_specs.tnn_column_size() == 0


def _effective_engine(engine: str,
                      column_counts: ColumnCounts = None) -> str:
    """The engine that can actually run for ``engine`` given the ambient
    mesh. The Pallas engines pass through when every column count in
    ``column_counts`` is :func:`_pallas_shardable` (the shard_map fast
    path serves them); otherwise — replication fallback, a 2-D bank, or an
    unknown shape (``column_counts=None``) — the answer is the bit-exact
    jnp engine of the same sparsity class. Everything else passes through
    unconditionally. :meth:`EnginePolicy.resolve` takes that answer only
    for ``auto``; an explicit Pallas request it cannot serve raises.

    ``column_counts`` is one count (a single bank call), an iterable of
    per-layer counts (the serve engine resolving for a whole network), or
    ``None`` for "shape unknown" (conservative: degrade under a mesh).
    """
    if engine not in ("pallas", "pallas_compact") or not mesh_active():
        return engine
    if column_counts is not None:
        counts = ((column_counts,) if isinstance(column_counts, int)
                  else tuple(column_counts))
        if counts and all(_pallas_shardable(c) for c in counts):
            return engine
    return "event" if engine == "pallas_compact" else "closed_form"


class BankShape(NamedTuple):
    """Workload of one neuron-bank evaluation, as the predictor sees it.

    pairs:   volley x neuron evaluations (B*Q, summed over columns).
    n_lines: dendritic input lines per neuron (n; the receptive field).
    t_steps: gamma-cycle length in ticks (T).
    """

    pairs: int
    n_lines: int
    t_steps: int


class Resolution(NamedTuple):
    """What :meth:`EnginePolicy.resolve` decided, and why.

    engine:       the engine that will run (post mesh degradation, which
                  only ``auto`` undergoes).
    requested:    the pre-degradation pick — the explicit backend name, or
                  the policy's cost/threshold choice for ``auto``.
    width:        compaction bucket width for the sparse engines (None
                  when the active-line count is unknown — concrete callers
                  then measure exactly, traced callers must supply one).
    predicted_us: per-candidate predicted runtime for the decision taken
                  ({} when no prediction was needed: explicit backend,
                  TPU preemption, density mode, or unknown workload).
    """

    engine: str
    requested: str
    width: Optional[int]
    predicted_us: Dict[str, float]


@dataclasses.dataclass(frozen=True)
class CostCoefficients:
    """Calibrated work-model coefficients (module docstring).

    Defaults are the committed fit against the full-size artifacts
    (BENCH_sparsity for closed_form/event, BENCH_pipeline for the scan
    ratio); ``pallas_unit_us`` is a fused-kernel prior (~8x the closed
    form's arithmetic intensity) — it only ranks candidates on TPU, where
    no committed CPU artifact can calibrate it.
    """

    #: us per pair*tick*line, dense closed form (median committed row).
    closed_form_unit_us: float = 5.34e-3
    #: us per pair*tick*line, tick-scan hardware mirror (1.45x closed form,
    #: the committed pipeline depth-1 ratio).
    scan_unit_us: float = 7.74e-3
    #: us per pair, fixed event-engine overhead (least-squares intercept).
    event_pair_us: float = 0.093
    #: us per pair*breakpoint; the sorted width is m = 2*s for s compacted
    #: lines (least-squares slope; the log factor is folded in).
    event_breakpoint_us: float = 0.192
    #: us per pair*tick*line, fused Pallas sweep (prior, not a fit).
    pallas_unit_us: float = 6.7e-4

    def predict_us(self, engine: str, shape: BankShape,
                   width: Optional[int] = None) -> float:
        """Predicted wall-clock (us) for one bank evaluation.

        ``width`` is the compacted width for the sparse engines; ``None``
        means uncompacted (sort all ``2 * n_lines`` breakpoints).
        """
        dense_units = shape.pairs * shape.t_steps * shape.n_lines
        if engine == "closed_form":
            return self.closed_form_unit_us * dense_units
        if engine == "scan":
            return self.scan_unit_us * dense_units
        if engine == "pallas":
            return self.pallas_unit_us * dense_units
        if engine in SPARSE_ENGINES:
            s = shape.n_lines if width is None else min(width, shape.n_lines)
            m = 2 * max(int(s), 1)
            return shape.pairs * (self.event_pair_us
                                  + self.event_breakpoint_us * m)
        raise ValueError(f"unknown engine {engine!r}")


def fit_coefficients(rows: Iterable[dict], *, pairs: int, n_lines: int,
                     t_steps: int,
                     base: Optional[CostCoefficients] = None
                     ) -> CostCoefficients:
    """Re-derive the event/closed-form coefficients from a BENCH_sparsity
    result list (the committed artifact's ``results`` array).

    The bench places exactly ``round(density * n)`` spiking lines per
    volley, so each event row's compacted bucket width — and hence its
    sorted breakpoint count ``m`` — is known: compacted rows use
    ``2 * bucket_width(s)``, uncompacted (``event_nc``) rows ``2 * n``.
    ``closed_form`` takes the median row (one workload, six densities);
    the event model is the least-squares affine fit in ``pairs * m``.
    Scan/pallas coefficients carry over from ``base`` (they are not in
    this sweep).
    """
    closed, event_pts = [], []
    for row in rows:
        us = row.get("us_per_call")
        backend = row.get("backend")
        density = row.get("density")
        if not isinstance(us, (int, float)) or density is None:
            continue
        if backend == "closed_form":
            closed.append(float(us))
        elif backend in ("event", "event_nc"):
            s = max(int(round(float(density) * n_lines)), 1)
            w = n_lines if backend == "event_nc" \
                else min(compaction.bucket_width(s), n_lines)
            event_pts.append((pairs * 2 * w, float(us)))
    if not closed or len(event_pts) < 2:
        raise ValueError("need closed_form rows and >=2 event rows to fit")
    closed.sort()
    mid = len(closed) // 2
    median = (closed[mid] if len(closed) % 2
              else 0.5 * (closed[mid - 1] + closed[mid]))
    c_cf = median / (pairs * t_steps * n_lines)
    xbar = sum(x for x, _ in event_pts) / len(event_pts)
    ybar = sum(y for _, y in event_pts) / len(event_pts)
    sxx = sum((x - xbar) ** 2 for x, _ in event_pts)
    sxy = sum((x - xbar) * (y - ybar) for x, y in event_pts)
    slope = sxy / sxx
    intercept = max((ybar - slope * xbar) / pairs, 0.0)
    base = base if base is not None else CostCoefficients()
    return dataclasses.replace(base, closed_form_unit_us=c_cf,
                               event_pair_us=intercept,
                               event_breakpoint_us=slope)


@dataclasses.dataclass(frozen=True)
class EnginePolicy:
    """Engine + compaction-width selection, in one host-side object.

    ``mode="cost"`` (default) ranks the candidates by
    :meth:`CostCoefficients.predict_us` at the measured workload;
    ``mode="density"`` reproduces the legacy ``DENSITY_EVENT_MAX``
    threshold exactly (the escape hatch, and what the deprecated
    ``resolve_backend`` wrapper delegates to). Both modes keep the
    non-negotiable parts of resolution: explicit names run as named or
    raise, TPU preempts ``auto`` with the fused Pallas kernel, mesh
    degradation applies last and to ``auto`` only, and an unknown workload
    stays dense.

    Frozen (hashable) so a policy can ride on the frozen layer configs and
    key jit-variant caches; construction is cheap, but prefer the memoized
    :func:`default_policy` / :func:`density_policy` accessors on hot paths.
    """

    mode: str = "cost"
    coeffs: CostCoefficients = CostCoefficients()
    density_event_max: float = DENSITY_EVENT_MAX

    def __post_init__(self):
        if self.mode not in ("cost", "density"):
            raise ValueError(
                f"unknown policy mode {self.mode!r}: expected 'cost' or "
                f"'density'")

    # ---------------------------------------------------------------- API

    def wants_density(self, backend: Backend,
                      column_counts: ColumnCounts = None) -> bool:
        """Whether :meth:`resolve` can use a measured density/active count
        for ``backend`` — False for explicit names and when the TPU Pallas
        fast path preempts, so callers skip the reduction + host sync."""
        return backend == "auto" and not self._pallas_preempts(column_counts)

    def resolve(self, backend: Backend = "auto", *,
                density: Optional[float] = None,
                max_active: Optional[int] = None,
                column_counts: ColumnCounts = None,
                shape: Optional[BankShape] = None) -> Resolution:
        """Resolve ``backend`` to the engine that should run.

        This is the successor of the ``resolve_backend`` /
        ``effective_engine`` / ``pallas_shardable`` trio: one call takes
        the measured workload (``density`` and/or ``max_active``, both
        ``None`` under tracing), the column structure (for the mesh
        capability check) and the bank shape (for the predictor), and
        returns the :class:`Resolution` — engine, pre-degradation request,
        compaction width, and the predictions behind the choice.

        Raises ValueError when an explicit Pallas backend cannot run under
        the ambient mesh (a column count that does not tile its
        ``column`` axis, a 2-D bank, or an unknown shape): only ``auto``
        may fall back to a jnp engine.
        """
        predicted: Dict[str, float] = {}
        s_active = self._active_lines(density, max_active, shape)
        if backend != "auto":
            requested = backend
        elif self._pallas_preempts(column_counts):
            requested = "pallas"
        elif self.mode == "density":
            requested = ("event" if density is not None
                         and density <= self.density_event_max
                         else "closed_form")
        elif s_active is None or shape is None:
            # unknown workload (tracing / no shape info): keep the dense
            # choice, exactly the legacy fallback
            requested = "closed_form"
        else:
            width = self.width_for(s_active, shape)
            predicted = {
                "event": self.coeffs.predict_us("event", shape, width),
                "closed_form": self.coeffs.predict_us("closed_form", shape),
            }
            # dict order breaks exact ties toward the sparse engine
            requested = min(predicted, key=predicted.__getitem__)
        engine = _effective_engine(requested, column_counts)
        if engine != requested and backend != "auto":
            raise ValueError(
                f"backend={backend!r} cannot run under the active mesh for "
                f"column counts {column_counts!r} (the shard_map kernels "
                f"need 3-D column stacks that tile the "
                f"{sharding_specs.TNN_COLUMN_AXIS!r} axis, size "
                f"{sharding_specs.tnn_column_size()}); use backend='auto' "
                f"to allow the jnp fallback")
        width = (self.width_for(s_active, shape)
                 if engine in SPARSE_ENGINES and s_active is not None
                 else None)
        return Resolution(engine=engine, requested=requested, width=width,
                          predicted_us=predicted)

    def width_for(self, max_active: int,
                  shape: Optional[BankShape] = None) -> int:
        """Cost-chosen compaction width covering ``max_active`` lines.

        Candidates are the bucket-ladder rungs at or above the measured
        count (:func:`compaction.bucket_width` keeps jit variants few);
        the predictor ranks them. The event cost is monotone in the
        width, so this resolves to the smallest covering rung — kept as
        an explicit argmin so a future non-monotone model (e.g. a
        lane-utilization term) changes the choice here and nowhere else.
        """
        s = max(int(max_active), 1)
        cover = compaction.bucket_width(s)
        if shape is None:
            return cover
        rungs = {cover, compaction.bucket_width(cover + 1)}
        return min(sorted(rungs),
                   key=lambda w: self.coeffs.predict_us("event", shape, w))

    # ----------------------------------------------------------- internals

    def _pallas_preempts(self, column_counts: ColumnCounts) -> bool:
        """TPU fast path: the fused kernel preempts measurement-driven
        selection whenever it can actually run (DESIGN.md §3.3)."""
        return (jax.default_backend() == "tpu"
                and _effective_engine("pallas", column_counts) == "pallas")

    def _active_lines(self, density: Optional[float],
                      max_active: Optional[int],
                      shape: Optional[BankShape]) -> Optional[int]:
        """Best available per-volley active-line count: the measured max
        when given, else a conservative (ceil) estimate from density."""
        if max_active is not None:
            return int(max_active)
        if density is not None and shape is not None:
            return min(int(math.ceil(density * shape.n_lines)),
                       shape.n_lines)
        return None


@functools.lru_cache(maxsize=None)
def _policy_for_mode(mode: str) -> EnginePolicy:
    return EnginePolicy(mode=mode)


def default_policy() -> EnginePolicy:
    """The memoized cost-driven policy (committed coefficients)."""
    return _policy_for_mode("cost")


def density_policy() -> EnginePolicy:
    """The memoized legacy density-threshold policy (escape hatch)."""
    return _policy_for_mode("density")


def get_policy(spec: Union[str, EnginePolicy]) -> EnginePolicy:
    """Validate/normalize a policy spec: ``"cost"``, ``"density"``, or an
    :class:`EnginePolicy` instance (config-time validation, like backend
    names — a typo fails at construction, not step time)."""
    if isinstance(spec, EnginePolicy):
        return spec
    if spec == "cost":
        return default_policy()
    if spec == "density":
        return density_policy()
    raise ValueError(
        f"unknown engine policy {spec!r}: expected 'cost', 'density', or "
        f"an EnginePolicy instance")
