"""MixedKernelSVM: the sklearn-style estimator wrapping Algorithm 1.

The paper's deliverable is a *machine*: a bank of OvO classifiers (digital
linear, digital RBF, analog sech2) feeding a decision encoder.  This module
exposes it as one first-class object:

    est = MixedKernelSVM(n_epochs=120).fit(x_train, y_train)
    est.score(x_test, y_test)                    # float (software) accuracy
    machine = est.deploy("circuit")              # CompiledMachine, one jit path
    machine.predict(x)                           # batched labels
    est.save("models/balance")                   # npz + json, no retraining
    est2 = MixedKernelSVM.load("models/balance")

``fit`` runs the separation-driven mixed-kernel exploration (Algorithm 1,
``selection.train_pairs``) with hardware-in-the-loop co-optimization of the
analog-bound classifiers, then assembles every Table-II design point
(``selection.build_banks``).  ``bank(target)`` returns the legacy object bank
(used by the hardware cost model); ``deploy(target)`` lowers it to a
:class:`~repro.api.compiled.CompiledMachine` (cached per target).

Targets: ``'float'`` (mixed software), ``'circuit'`` (mixed deployed:
digital linear + analog RBF), ``'linear'`` (all-digital-linear baseline),
``'rbf'`` (all-digital-RBF baseline), plus ``'linear_float'``/``'rbf_float'``.

Beyond Algorithm 1's single design point, the estimator fronts the batched
kernel-assignment design space (``repro.core.dse``, DESIGN.md §5):

    front = est.pareto(x_val, y_val)             # accuracy/area/power front
    machine = est.deploy("circuit",
                         area_budget=0.1,        # mm^2
                         power_budget=0.05)      # mW -> cheapest point in budget
    est.assignment_                              # chosen per-pair kernel map
    est.save("models/balance")                   # assignment round-trips

``deploy("circuit")`` with no budget remains exactly the Algorithm-1
machine.

Process variation (DESIGN.md §6) is a first-class axis:

    mc = est.monte_carlo(x_val, y_val, n_variants=64,
                         key=jax.random.PRNGKey(7))   # per-variant stats
    mc.mean, mc.worst, mc.yield_at(0.9)
    front = est.pareto(x_val, y_val, n_variants=64)   # robust sweep
    machine = est.deploy("circuit", yield_floor=0.95) # cheapest in-spec
    est.save("models/balance")                        # assignment + MC key

and ``MixedKernelSVM(circuit=CircuitParams(sigma_vth=...))`` overrides the
analog process corner without touching internals (serialized, since the hw
model stays deterministic in ``(seed, circuit)``).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

import jax
import numpy as np

from repro.api.compiled import (
    MC_STREAM_CHUNK,
    CompiledMachine,
    MonteCarloMachine,
    StreamingMCMachine,
    _key_data,
    _strip_ext,
    compile_candidates,
    compile_machine,
    compile_mc_stream,
    compile_variants,
)
from repro.core import dse as dse_mod
from repro.core import hwcost, mcstream, selection
from repro.core.analog import (
    AnalogBinaryClassifier,
    AnalogRBFModel,
    CircuitParams,
)
from repro.core.ovo import DigitalLinearClassifier, MulticlassSVM
from repro.core.svm import SVMModel

# v2: config gained "hw_all", meta gained "assignment" (the chosen kernel
# map of a budgeted deploy).  v3: config gained "circuit" (CircuitParams
# overrides) and meta gained "monte_carlo" (the MC key/config of a
# variation-aware sweep).  Older saves load fine (missing keys default).
_FORMAT_VERSION = 3

_MODEL_SLOTS = ("model_linear", "model_rbf", "model_hw")
_MODEL_ARRAYS = ("support_x", "support_y", "alpha", "w")


@dataclasses.dataclass(frozen=True)
class MonteCarloResult:
    """Per-variant accuracy of one deployed assignment (DESIGN.md §6.5).

    ``accuracy[0]`` is the nominal (zero-offset) instance; the remaining
    rows are sampled fabricated instances.  ``key_data`` is the raw jax
    PRNG key the mismatch was drawn with — enough to reproduce the exact
    variant set.
    """

    accuracy: np.ndarray      # (V,) per-variant validation accuracy
    assignment: list          # per-pair kernel map evaluated
    n_variants: int
    sigma_scale: float
    key_data: list

    @property
    def nominal(self) -> float:
        return float(self.accuracy[0])

    @property
    def mean(self) -> float:
        return float(np.mean(self.accuracy))

    @property
    def std(self) -> float:
        return float(np.std(self.accuracy))

    @property
    def worst(self) -> float:
        return float(np.min(self.accuracy))

    def yield_at(self, accuracy_floor: float) -> float:
        """Fraction of instances at or above the accuracy floor."""
        return float(np.mean(self.accuracy >= accuracy_floor))


#: Dense Monte-Carlo above this many variants silently switches to the
#: flat-memory streaming engine (DESIGN.md §10): the dense ``(V, n, P, 2)``
#: bit tensor it would otherwise materialize stops fitting long before 10^6.
STREAM_AUTO_VARIANTS = 4096


@dataclasses.dataclass(frozen=True)
class StreamingMCResult:
    """Streamed tail-yield statistics of one assignment (DESIGN.md §10).

    Produced by :meth:`MixedKernelSVM.monte_carlo` when the streaming
    engine runs (``method=`` given, or ``n_variants`` above
    :data:`STREAM_AUTO_VARIANTS`).  Unlike :class:`MonteCarloResult` the
    per-variant accuracies are never materialized: ``mean``/``std`` are
    streamed Welford moments, ``yield_`` carries a ``(yield_lo,
    yield_hi)`` confidence interval, and quantiles come from a fixed-grid
    histogram sketch (exact to the accuracy grid of the validation set).
    """

    mean: float
    std: float
    worst: float              # streamed min over sampled variants
    best: float
    yield_: float             # point estimate (IS: self-normalized)
    yield_lo: float           # lower/upper confidence bound at `confidence`
    yield_hi: float
    n_eff: float              # effective sample size (== V unless IS)
    accuracy_floor: float
    confidence: float
    ci: str                   # "wilson" | "clopper-pearson"
    n_variants: int
    method: str               # "iid" | "sobol" | "stratified" | "is"
    sigma_scale: float
    is_scale: float
    assignment: list
    key_data: list
    hist: np.ndarray          # (n_bins,) weighted accuracy histogram

    def quantile(self, q) -> np.ndarray:
        """Accuracy quantile(s) from the streamed histogram sketch."""
        qs = np.atleast_1d(np.asarray(q, np.float64))
        out = mcstream.hist_quantiles(self.hist[None, :], qs)[:, 0]
        return out if np.ndim(q) else out[0]


class MixedKernelSVM:
    """Mixed-kernel mixed-signal OvO SVM (paper Algorithm 1 + deployment).

    Parameters mirror the old ``selection.explore`` signature.  ``hw`` may be
    a pre-calibrated :class:`AnalogRBFModel`; by default one is calibrated
    from the circuit surrogate with ``seed`` (deterministic, and therefore
    serializable — ``save`` requires the default construction).
    """

    def __init__(
        self,
        weight_bits: int = 8,
        input_bits: int = 4,
        n_epochs: int = 200,
        seed: int = 0,
        tie_margin: float = 0.005,
        alpha_floor_rel: float = 1.0 / 256.0,
        cv_epochs: Optional[int] = None,
        hw: Optional[AnalogRBFModel] = None,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None,
        mesh=None,
        hw_all: bool = True,
        circuit: Optional[CircuitParams] = None,
    ):
        self.weight_bits = weight_bits
        self.input_bits = input_bits
        self.n_epochs = n_epochs
        self.seed = seed
        self.tie_margin = tie_margin
        self.alpha_floor_rel = alpha_floor_rel
        # Epochs used when training CV folds during the hyper-parameter
        # search; None keeps the historical max(60, n_epochs // 2) policy.
        self.cv_epochs = cv_epochs
        self.use_pallas = use_pallas
        # Pallas-interpreter override for the compiled paths (None = the
        # kernels.ops backend default); runtime-only, like `use_pallas`.
        self.interpret = interpret
        # Optional device mesh for the batched trainer's shard_map variant
        # (runtime-only, like `hw`/`use_pallas`: not serialized).
        self.mesh = mesh
        # Keep the hardware co-optimized candidate for EVERY pair (free in
        # the batched engine) so the kernel-assignment design space has an
        # RBF-analog candidate per pair; False restores the lean saves.
        self.hw_all = hw_all
        # Circuit-parameter overrides for the analog behavioral model
        # (sigma sweeps, bias studies) WITHOUT touching internals: the hw
        # model is calibrated from `(seed, circuit)` deterministically, so
        # — unlike a user-supplied `hw` object — it serializes.
        self.circuit = circuit
        self._custom_hw = hw is not None
        self.hw_ = hw
        self.pairs_: Optional[list[selection.PairResult]] = None
        self.n_classes_: Optional[int] = None
        self._banks: Optional[dict[str, MulticlassSVM]] = None
        self._compiled: dict[str, CompiledMachine] = {}
        # DSE state: the chosen per-pair kernel map of a budgeted deploy
        # (serialized), the cached sweep result and design space (not).
        self.assignment_: Optional[list[str]] = None
        self.pareto_: Optional[dse_mod.SweepResult] = None
        self._dse: Optional[dse_mod.DesignSpace] = None
        self._dse_cm: Optional[hwcost.CostModel] = None
        self._candidate_cache = None
        self._candidate_machine = None
        # Monte-Carlo state: compiled variant machines keyed by their
        # sampling config (cached per fit), plus the serialized MC config
        # of the last variation-aware sweep (key data, n_variants, ...).
        self._mc_machines: dict[tuple, MonteCarloMachine] = {}
        self._stream_machines: dict[tuple, StreamingMCMachine] = {}
        self.mc_state_: Optional[dict] = None

    # -- fitting --------------------------------------------------------------

    def fit(self, x: np.ndarray, y: np.ndarray) -> "MixedKernelSVM":
        """Run Algorithm 1 and deploy every design point.

        Labels must be contiguous integers 0..K-1 with every class present
        (an absent class would silently train its OvO pairs on empty
        subsets).
        """
        y = np.asarray(y)
        classes = np.unique(y)
        if classes.size < 2 or not np.array_equal(
                classes, np.arange(classes.size)):
            raise ValueError(
                "labels must be contiguous integers 0..K-1 with K >= 2 and "
                f"every class present; got classes {classes.tolist()}")
        self.n_classes_ = int(classes.size)
        if self.hw_ is None:
            self.hw_ = selection.default_hw(self.seed, self.circuit)
        self.pairs_ = selection.train_pairs(
            np.asarray(x), y, self.n_classes_, hw=self.hw_,
            n_epochs=self.n_epochs, seed=self.seed,
            tie_margin=self.tie_margin, cv_epochs=self.cv_epochs,
            mesh=self.mesh, hw_all=self.hw_all,
            use_pallas=self.use_pallas, interpret=self.interpret)
        self.assignment_ = None
        self.pareto_ = None
        self.mc_state_ = None
        self._build()
        return self

    def _build(self) -> None:
        """(Re)assemble the object banks from trained pairs."""
        self._banks = selection.build_banks(
            self.pairs_, self.n_classes_, hw=self.hw_,
            weight_bits=self.weight_bits, input_bits=self.input_bits,
            seed=self.seed, alpha_floor_rel=self.alpha_floor_rel)
        self._compiled = {}
        self._dse = None
        self._dse_cm = None
        self._candidate_cache = None
        self._candidate_machine = None
        self._mc_machines = {}
        self._stream_machines = {}

    def _check_fitted(self) -> None:
        if self._banks is None:
            raise RuntimeError("MixedKernelSVM is not fitted; call fit(x, y)")

    # -- introspection ---------------------------------------------------------

    @property
    def kernel_map_(self) -> list[str]:
        self._check_fitted()
        return [p.kernel for p in self.pairs_]

    @property
    def n_rbf_(self) -> int:
        return sum(k == "rbf" for k in self.kernel_map_)

    @property
    def targets(self) -> tuple[str, ...]:
        return selection.BANK_TARGETS

    # -- deployment ------------------------------------------------------------

    def bank(self, target: str = "float") -> MulticlassSVM:
        """The legacy per-classifier object bank for ``target`` (used by the
        hardware cost model and as the reference path in tests)."""
        self._check_fitted()
        if target not in self._banks:
            raise KeyError(
                f"unknown target {target!r}; one of {selection.BANK_TARGETS}")
        return self._banks[target]

    def deploy(
        self,
        target: str = "float",
        area_budget: Optional[float] = None,
        power_budget: Optional[float] = None,
        yield_floor: Optional[float] = None,
        yield_confidence: Optional[float] = 0.95,
        decider: str = "votes",
    ) -> CompiledMachine:
        """Lower ``target``'s bank to one batched jit inference path.

        ``decider="dag"`` compiles the O(K) DDAG decision front instead of
        the dense votes path (DESIGN.md §11) — same banks, K-1 pair
        evaluations per sample at predict time.

        With an ``area_budget`` (mm^2) and/or ``power_budget`` (mW) —
        ``'circuit'`` target only — the deployment instead picks the
        cheapest Pareto point of the kernel-assignment design space that
        meets the budget (requires a prior :meth:`pareto` sweep), records
        its per-pair kernel map in ``assignment_`` (serialized by
        ``save``), and compiles that machine.  With no budget the
        Algorithm-1 machine is returned unchanged.

        ``yield_floor`` (requires a prior Monte-Carlo :meth:`pareto`
        sweep, ``n_variants=...``) switches to the robust rule: the
        CHEAPEST budget-feasible design whose yield — fraction of sampled
        fabricated instances at or above the sweep's accuracy floor —
        meets the floor (``SweepResult.select``).  The gate is the
        Wilson LOWER confidence bound of the sampled yield at
        ``yield_confidence`` (default 95%), so a design only deploys when
        the evidence — not just the point estimate — supports the floor;
        ``yield_confidence=None`` restores the historical point-estimate
        rule.
        """
        if area_budget is None and power_budget is None \
                and yield_floor is None:
            key = target if decider == "votes" else f"{target}@{decider}"
            if key not in self._compiled:
                self._compiled[key] = compile_machine(
                    self.bank(target), use_pallas=self.use_pallas,
                    interpret=self.interpret, decider=decider)
            return self._compiled[key]
        if target != "circuit":
            raise ValueError(
                "budget-constrained deployment explores the circuit design "
                f"space; got target {target!r}")
        if self.pareto_ is None:
            raise RuntimeError(
                "no Pareto front available: call est.pareto(x_val, y_val) "
                "before deploying against a budget")
        i = self.pareto_.select(area_budget=area_budget,
                                power_budget=power_budget,
                                yield_floor=yield_floor,
                                confidence=yield_confidence)
        self.assignment_ = self.pareto_.kernel_map(i)
        if yield_floor is not None and self.mc_state_ is not None:
            self.mc_state_["yield_floor"] = float(yield_floor)
            self.mc_state_["yield_confidence"] = (
                None if yield_confidence is None else float(yield_confidence))
        return self.deploy_assignment(self.assignment_, decider=decider)

    # -- kernel-assignment design space (DESIGN.md §5) -------------------------

    def _candidates(self) -> list[tuple]:
        """Per-pair (linear-digital, RBF-analog) deployed candidates — the
        same constructions ``build_banks`` uses, so the Algorithm-1
        assignment reproduces the ``'circuit'`` bank classifier-for-
        classifier.  Cached per fit (deployment re-quantizes weights)."""
        self._check_fitted()
        if self._candidate_cache is None:
            missing = [p.pair for p in self.pairs_ if p.model_hw is None]
            if missing:
                raise RuntimeError(
                    f"pairs {missing} have no hardware co-optimized "
                    "candidate; fit with hw_all=True (the default) to "
                    "explore the assignment space")
            self._candidate_cache = [
                (DigitalLinearClassifier.deploy(
                    p.model_linear, self.weight_bits, self.input_bits),
                 AnalogBinaryClassifier.deploy(
                    p.model_hw, self.hw_,
                    alpha_floor_rel=self.alpha_floor_rel))
                for p in self.pairs_
            ]
        return self._candidate_cache

    def design_space(
        self, cm: Optional[hwcost.CostModel] = None
    ) -> dse_mod.DesignSpace:
        """The batched design space over per-pair kernel assignments.

        The jitted candidate machine is cost-model-independent and cached
        per fit; only the (numpy) cost table is rebuilt when ``cm``
        changes, so re-sweeping under a recalibrated cost model is cheap.
        """
        cm = cm or hwcost.CostModel()
        if self._dse is None or self._dse_cm != cm:
            if self._candidate_machine is None:
                self._candidate_machine = compile_candidates(
                    self._candidates(), self.n_classes_,
                    use_pallas=self.use_pallas, interpret=self.interpret)
            table = hwcost.pair_cost_table(self._candidates(), cm,
                                           n_classes=self.n_classes_)
            self._dse = dse_mod.DesignSpace(
                self._candidate_machine, table, self.n_classes_)
            self._dse_cm = cm
        return self._dse

    def pareto(
        self,
        x_val: np.ndarray,
        y_val: np.ndarray,
        cm: Optional[hwcost.CostModel] = None,
        n_variants: Optional[int] = None,
        key: Optional[jax.Array] = None,
        sigma_scale: float = 1.0,
        accuracy_floor: Optional[float] = None,
        **sweep_kwargs,
    ) -> dse_mod.SweepResult:
        """Sweep the kernel-assignment space on validation data and return
        the accuracy/area/power Pareto front (cached in ``pareto_``).

        Exhaustive ``2^P`` for ``P <= 12`` (two jit compiles: the candidate
        bit tensor + the bit-recombination program); seeded greedy/flip
        search beyond, seeded with the Algorithm-1 assignment.

        Monte-Carlo mode (``n_variants=``): every assignment additionally
        gets mean/std/worst-case accuracy and yield over ``n_variants``
        sampled fabricated instances, and the result carries the robust
        four-objective front — still two jit compiles (the MC forward +
        the batched recombination).  ``key`` is the explicit mismatch
        PRNG key (default ``PRNGKey(self.seed)``); ``accuracy_floor``
        defaults to two points below the nominal Algorithm-1 circuit
        accuracy on the given validation set.  The MC config (key data,
        ``n_variants``, ``sigma_scale``, floor) is recorded in
        ``mc_state_`` and serialized by :meth:`save`.
        """
        space = self.design_space(cm)
        seeds = sweep_kwargs.pop("seeds", dse_mod.assignment_from_kernel_map(
            self.kernel_map_)[None, :])
        mc_machine = None
        if n_variants is not None:
            if key is None:
                key = jax.random.PRNGKey(self.seed)
            if accuracy_floor is None:
                accuracy_floor = self.score(x_val, y_val,
                                            target="circuit") - 0.02
            mc_machine = self.monte_carlo_machine(
                n_variants, key, sigma_scale=sigma_scale)
        elif accuracy_floor is not None:
            raise ValueError(
                "accuracy_floor only applies to Monte-Carlo sweeps; pass "
                "n_variants=... as well")
        self.pareto_ = space.sweep(np.asarray(x_val), np.asarray(y_val),
                                   seeds=seeds, mc_machine=mc_machine,
                                   accuracy_floor=accuracy_floor,
                                   **sweep_kwargs)
        if mc_machine is not None:
            self.mc_state_ = {
                "key_data": np.asarray(mc_machine.key_data).tolist(),
                "n_variants": int(n_variants),
                "sigma_scale": float(sigma_scale),
                "accuracy_floor": float(accuracy_floor),
            }
        return self.pareto_

    # -- Monte-Carlo variation (DESIGN.md §6) -----------------------------------

    def monte_carlo_machine(
        self,
        n_variants: int,
        key: jax.Array,
        sigma_scale: float = 1.0,
    ) -> MonteCarloMachine:
        """The compiled variant machine for this estimator's candidates:
        ``pair_bits(x) -> (V, n, P, 2)`` in one jitted forward, variant 0
        nominal.  Cached per ``(n_variants, key, sigma_scale)`` so repeated
        sweeps/evaluations with one config compile once."""
        self._check_fitted()
        cache_key = (int(n_variants),
                     _key_data(key).tobytes(), float(sigma_scale))
        if cache_key not in self._mc_machines:
            self._mc_machines[cache_key] = compile_variants(
                self._candidates(), self.n_classes_, key=key,
                n_variants=n_variants, sigma_scale=sigma_scale,
                use_pallas=self.use_pallas, interpret=self.interpret)
        return self._mc_machines[cache_key]

    def stream_machine(
        self,
        key: jax.Array,
        method: str = "iid",
        mc_chunk: int = MC_STREAM_CHUNK,
        sigma_scale: float = 1.0,
        is_scale: float = 2.0,
    ) -> StreamingMCMachine:
        """The flat-memory streaming MC engine for this estimator's
        candidates (DESIGN.md §10): one compiled donated step regardless
        of the variant count.  Cached per sampling config so repeated
        calls with one config compile once."""
        self._check_fitted()
        cache_key = (_key_data(key).tobytes(), str(method), int(mc_chunk),
                     float(sigma_scale), float(is_scale))
        if cache_key not in self._stream_machines:
            self._stream_machines[cache_key] = compile_mc_stream(
                self._candidates(), self.n_classes_, key=key,
                method=method, mc_chunk=mc_chunk, sigma_scale=sigma_scale,
                is_scale=is_scale, use_pallas=self.use_pallas,
                interpret=self.interpret)
        return self._stream_machines[cache_key]

    def monte_carlo(
        self,
        x: np.ndarray,
        y: np.ndarray,
        n_variants: int = 64,
        key: Optional[jax.Array] = None,
        sigma_scale: float = 1.0,
        assignment: Optional[list] = None,
        method: Optional[str] = None,
        mc_chunk: Optional[int] = None,
        accuracy_floor: Optional[float] = None,
        is_scale: float = 2.0,
        confidence: float = 0.95,
        ci: str = "wilson",
        mesh=None,
    ) -> object:
        """Accuracy of ONE deployed assignment under sampled process
        variation.

        ``assignment`` defaults to the estimator's current circuit
        assignment (``assignment_`` from a budgeted/yield deploy if set,
        else the Algorithm-1 kernel map).  ``key`` is the explicit
        mismatch key (default ``PRNGKey(self.seed)``); the key data is
        recorded in the result for reproducibility.

        Two engines sit behind this call (DESIGN.md §10):

        * **dense** (default for small ``n_variants``): one jitted
          forward materializes every variant's pair bits and returns a
          :class:`MonteCarloResult` with the raw ``(V,)`` accuracy
          vector (variant 0 nominal).
        * **streaming** (``method="iid" | "sobol" | "stratified" |
          "is"``, or any ``n_variants`` above
          :data:`STREAM_AUTO_VARIANTS`): fixed-shape chunks of
          ``mc_chunk`` variants are generated on the fly and folded into
          constant-size accumulators, so ``n_variants=10**6`` runs in
          the same device memory as 64.  Returns a
          :class:`StreamingMCResult` with Wilson/Clopper-Pearson yield
          bounds against ``accuracy_floor`` (default: two points below
          the nominal circuit accuracy on ``(x, y)``).  ``mesh`` (from
          :func:`repro.launch.mesh.make_variant_mesh`) shards each chunk
          over a 1-D ``"variants"`` device axis.
        """
        self._check_fitted()
        if key is None:
            key = jax.random.PRNGKey(self.seed)
        if assignment is None:
            assignment = self.assignment_ or self.kernel_map_
        kmap = [k if isinstance(k, str) else ("rbf" if k else "linear")
                for k in list(assignment)]
        streaming = (method is not None or mc_chunk is not None
                     or mesh is not None
                     or int(n_variants) > STREAM_AUTO_VARIANTS)
        if not streaming:
            machine = self.monte_carlo_machine(n_variants, key,
                                               sigma_scale=sigma_scale)
            bits3 = machine.pair_bits(np.asarray(x))
            a = dse_mod.assignment_from_kernel_map(kmap)
            acc = dse_mod.assignment_accuracies_mc(
                bits3, a[None, :], np.asarray(y), self.n_classes_)[:, 0]
            return MonteCarloResult(
                accuracy=acc, assignment=kmap, n_variants=int(n_variants),
                sigma_scale=float(sigma_scale),
                key_data=np.asarray(machine.key_data).tolist())
        else:
            # Profiler spans (no-ops unless a profiler is tracing): the call,
            # and its host phases; `stream` opens the later ones.
            with jax.profiler.TraceAnnotation("repro.mc.stream") as span:
                with jax.profiler.TraceAnnotation("repro.mc.prep"):
                    if accuracy_floor is None:
                        accuracy_floor = self.score(
                            x, y, target="circuit") - 0.02
                    sm = self.stream_machine(
                        key, method=method or "iid",
                        mc_chunk=(MC_STREAM_CHUNK if mc_chunk is None
                                  else mc_chunk),
                        sigma_scale=sigma_scale, is_scale=is_scale)
                    a = dse_mod.assignment_from_kernel_map(kmap)
                b = sm._chunk_size(mesh)
                span.set_metadata(n_variants=int(n_variants), mc_chunk=b,
                                  steps=-(-int(n_variants) // b))
                out = sm.stream(np.asarray(x), np.asarray(y), a[None, :],
                                n_variants=int(n_variants),
                                accuracy_floor=float(accuracy_floor),
                                mesh=mesh, confidence=confidence, ci=ci)
        return StreamingMCResult(
            mean=float(out["mean"][0]), std=float(out["std"][0]),
            worst=float(out["worst"][0]), best=float(out["best"][0]),
            yield_=float(out["yield"][0]),
            yield_lo=float(out["yield_lo"][0]),
            yield_hi=float(out["yield_hi"][0]),
            n_eff=float(out["n_eff"]),
            accuracy_floor=float(accuracy_floor),
            confidence=float(confidence), ci=str(out["ci"]),
            n_variants=int(n_variants), method=sm.method,
            sigma_scale=float(sigma_scale), is_scale=float(is_scale),
            assignment=kmap,
            key_data=np.asarray(sm.key_data).tolist(),
            hist=np.asarray(out["hist"][0]))

    def deploy_assignment(
        self, assignment: Optional[list] = None, decider: str = "votes"
    ) -> CompiledMachine:
        """Compile the machine for an explicit per-pair kernel assignment
        (default: the stored ``assignment_`` of a budgeted deploy)."""
        self._check_fitted()
        if assignment is None:
            assignment = self.assignment_
        if assignment is None:
            raise RuntimeError(
                "no assignment chosen yet: pass one explicitly or deploy "
                "with a budget after est.pareto(...)")
        kmap = [k if isinstance(k, str) else ("rbf" if k else "linear")
                for k in list(assignment)]
        key = "assignment:" + "".join("r" if k == "rbf" else "l"
                                      for k in kmap)
        if decider != "votes":
            key += f"@{decider}"
        if key not in self._compiled:
            self._compiled[key] = compile_machine(
                self._assignment_bank(kmap), use_pallas=self.use_pallas,
                interpret=self.interpret, decider=decider)
        return self._compiled[key]

    def _assignment_bank(self, kmap: list[str]) -> MulticlassSVM:
        if len(kmap) != len(self.pairs_):
            raise ValueError(
                f"assignment has {len(kmap)} pairs, machine has "
                f"{len(self.pairs_)}")
        cands = self._candidates()
        classifiers = [c[1] if k == "rbf" else c[0]
                       for c, k in zip(cands, kmap)]
        return MulticlassSVM(n_classes=self.n_classes_,
                             classifiers=classifiers, kernel_map=kmap)

    # -- prediction ------------------------------------------------------------

    def predict(self, x: np.ndarray, target: str = "float") -> np.ndarray:
        return self.deploy(target).predict(x)

    def predict_bits(self, x: np.ndarray, target: str = "float") -> np.ndarray:
        return self.deploy(target).predict_bits(x)

    def score(self, x: np.ndarray, y: np.ndarray,
              target: str = "float") -> float:
        return float(np.mean(self.predict(x, target) == np.asarray(y)))

    # -- serialization (npz arrays + json structure) ----------------------------

    def save(self, path: str) -> None:
        """Write ``<path>.npz`` + ``<path>.json``; round-trips without
        retraining (deployments are rebuilt deterministically on load)."""
        self._check_fitted()
        if self._custom_hw:
            raise ValueError(
                "cannot serialize an estimator built around a user-supplied "
                "AnalogRBFModel; use the default hw (calibrated from `seed`)")
        path = _strip_ext(path)
        arrays: dict[str, np.ndarray] = {}
        meta_pairs = []
        for i, p in enumerate(self.pairs_):
            entry = {
                "pair": list(p.pair), "kernel": p.kernel,
                "acc_linear": p.acc_linear, "acc_rbf": p.acc_rbf,
                "models": {},
            }
            for slot in _MODEL_SLOTS:
                m: Optional[SVMModel] = getattr(p, slot)
                if m is None:
                    continue
                entry["models"][slot] = {
                    "kind": m.kind, "bias": m.bias, "gamma": m.gamma,
                    "c": m.c, "has_w": m.w is not None,
                }
                for name in _MODEL_ARRAYS:
                    a = getattr(m, name)
                    if a is not None:
                        arrays[f"p{i}.{slot}.{name}"] = np.asarray(a)
            meta_pairs.append(entry)
        meta = {
            "format": "repro.api.MixedKernelSVM",
            "version": _FORMAT_VERSION,
            "n_classes": self.n_classes_,
            "config": {
                "weight_bits": self.weight_bits,
                "input_bits": self.input_bits,
                "n_epochs": self.n_epochs,
                "seed": self.seed,
                "tie_margin": self.tie_margin,
                "alpha_floor_rel": self.alpha_floor_rel,
                "cv_epochs": self.cv_epochs,
                "hw_all": self.hw_all,
                "circuit": (None if self.circuit is None
                            else dataclasses.asdict(self.circuit)),
            },
            "assignment": self.assignment_,
            "monte_carlo": self.mc_state_,
            "pairs": meta_pairs,
        }
        np.savez(path + ".npz", **arrays)
        with open(path + ".json", "w") as f:
            json.dump(meta, f, indent=2)

    @classmethod
    def load(cls, path: str, use_pallas: Optional[bool] = None,
             interpret: Optional[bool] = None) -> "MixedKernelSVM":
        path = _strip_ext(path)
        with open(path + ".json") as f:
            meta = json.load(f)
        if meta.get("format") != "repro.api.MixedKernelSVM":
            raise ValueError(f"{path}.json is not a MixedKernelSVM save")
        if int(meta.get("version", 0)) > _FORMAT_VERSION:
            raise ValueError(
                f"{path}.json is format version {meta['version']}; this "
                f"build reads up to version {_FORMAT_VERSION} — upgrade "
                "the library to load it")
        npz = np.load(path + ".npz")
        config = dict(meta["config"])
        if config.get("circuit"):
            config["circuit"] = CircuitParams(**config["circuit"])
        est = cls(use_pallas=use_pallas, interpret=interpret, **config)
        est.n_classes_ = int(meta["n_classes"])
        est.hw_ = selection.default_hw(est.seed, est.circuit)

        def rebuild(i: int, slot: str, m_meta: dict) -> SVMModel:
            def arr(name):
                key = f"p{i}.{slot}.{name}"
                return npz[key] if key in npz else None

            kind = m_meta["kind"]
            return SVMModel(
                kind=kind,
                support_x=arr("support_x"), support_y=arr("support_y"),
                alpha=arr("alpha"), bias=float(m_meta["bias"]),
                gamma=float(m_meta["gamma"]), c=float(m_meta["c"]),
                w=arr("w") if m_meta["has_w"] else None,
                # hardware-in-the-loop models carry the calibrated kernel
                kernel_fn=est.hw_.kernel_response if kind == "hw" else None,
            )

        pairs = []
        for i, entry in enumerate(meta["pairs"]):
            models = {
                slot: rebuild(i, slot, m_meta)
                for slot, m_meta in entry["models"].items()
            }
            kernel = entry["kernel"]
            m_hw = models.get("model_hw")
            pairs.append(selection.PairResult(
                pair=tuple(entry["pair"]), kernel=kernel,
                model=m_hw if kernel == "rbf" else models["model_linear"],
                acc_linear=float(entry["acc_linear"]),
                acc_rbf=float(entry["acc_rbf"]),
                model_linear=models["model_linear"],
                model_rbf=models["model_rbf"], model_hw=m_hw,
            ))
        est.pairs_ = pairs
        assignment = meta.get("assignment")
        est.assignment_ = list(assignment) if assignment else None
        mc = meta.get("monte_carlo")
        est.mc_state_ = dict(mc) if mc else None
        est._build()
        return est

