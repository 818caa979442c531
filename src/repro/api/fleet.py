"""FleetMachine: many CompiledMachines co-batched into ONE jitted forward.

A deployed near-sensor installation is a *fleet*: many trained machines
(different datasets, circuit corners, tenants) answering continuous
small-query streams.  Serving them as separate ``CompiledMachine`` objects
means one device program per model per batch — a request mix of M models
costs M dispatches even when the total row count is tiny.

``compile_fleet`` concatenates member machines into one super-bank machine
with a single jitted forward

    ``forward(x (n, d_max) f32, model_idx (n,) i32)
        -> (labels (n,) i32, scores (n, P_total) f32)``

so ONE dispatch serves a batch whose rows belong to *any* mix of members.
Layout (DESIGN.md §9):

* **Shared padded input layout** — rows are padded on the feature axis to
  ``d_max = max(member.n_features)``; member ``m``'s banks read only
  ``x[:, :d_m]``, so the padding columns are dead for its own rows (and
  rows belonging to other members produce don't-care columns that the
  routing select discards).

* **Per-member pair/class slices** — every member's banks are carried
  VERBATIM (same grouping, same padded ``M``, same ``inv_perm``), and its
  score columns occupy the contiguous slice ``pair_slice(model_id)`` of
  the concatenated ``(n, P_total)`` tensor.  This is the bit-identity
  contract: re-grouping banks across members would change contraction
  padding and therefore f32 summation order, so the fleet instead
  replicates each member's exact forward subgraph and concatenates the
  results.  ``FleetMachine.predict(x, model)`` is bit-identical to
  ``member.predict(x)`` — scores, bits and labels.

* **Routing** — per-member labels are computed for all rows (the decision
  encoder is O(n) next to the kernel banks) and one
  ``take_along_axis(labels_stack, model_idx)`` selects each row's own
  member.  Un-padding on return is the serving engine's job.

The serving hot path is the labels-only program ``_labels_jit``, jitted
with ``donate_argnums=(1,)``: the ``model_idx`` input buffer (i32, (n,))
is donated and reused for the label output (i32, (n,)) — the donation the
static analyzer verifies (``DONATION-DROPPED``, DESIGN.md §8) and the
double-buffered engine staging relies on (``repro.serving.svm_engine``).
"""
from __future__ import annotations

import json
from typing import Mapping, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.compiled import (
    DECIDERS,
    CompiledMachine,
    _all_scores,
    _bank_arrays,
    _banks_from_entries,
    _dag_labels,
    _dag_row_maps,
    _dag_step_plans,
    _Decider,
    _strip_ext,
)
from repro.core.ovo import pair_index_matrix

_FLEET_FORMAT = "repro.api.FleetMachine"
_FLEET_VERSION = 1

ModelRef = Union[str, int]


class FleetMachine:
    """Co-batched multi-model machine (see module docstring).

    Construct via :func:`compile_fleet` or :meth:`FleetMachine.load`.
    """

    def __init__(self, model_ids: Sequence[str],
                 machines: Sequence[CompiledMachine],
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 decider: str = "votes"):
        if len(model_ids) != len(machines) or not machines:
            raise ValueError("need one model id per member machine (>= 1)")
        if len(set(model_ids)) != len(model_ids):
            raise ValueError(f"duplicate model ids: {list(model_ids)}")
        self.model_ids = [str(m) for m in model_ids]
        self._members = list(machines)
        self._index = {m: i for i, m in enumerate(self.model_ids)}
        self.n_models = len(self._members)
        self.n_features = max(m.n_features for m in self._members)
        self.n_pairs_total = sum(m.n_pairs for m in self._members)

        # Per-member column slices into the concatenated score tensor.
        offs = np.cumsum([0] + [m.n_pairs for m in self._members])
        self._pair_slices = [(int(offs[i]), int(offs[i + 1]))
                             for i in range(self.n_models)]

        # Inherit member dispatch settings when they agree (the common
        # case and what the bit-identity contract assumes); an explicit
        # argument or the backend default otherwise.
        if use_pallas is None:
            vals = {m.use_pallas for m in self._members}
            use_pallas = vals.pop() if len(vals) == 1 else \
                jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        if interpret is None:
            ivals = {m.interpret for m in self._members}
            interpret = ivals.pop() if len(ivals) == 1 else None
        self.interpret = interpret

        self._deciders = [_Decider.build(m.n_classes) for m in self._members]

        # Decision front of the labels path: dense votes (seed semantics)
        # or the per-member O(K) DAG elimination front.  The scores path
        # (`decision_scores`/`predict_bits`) always runs dense — it is
        # the bit-identity oracle either way.
        if decider not in DECIDERS:
            raise ValueError(
                f"unknown decider {decider!r}; one of {DECIDERS}")
        self.decider = decider
        if decider == "dag":
            self._pair_matrices = [
                jnp.asarray(pair_index_matrix(m.n_classes))
                for m in self._members]
            self._row_maps = [
                _dag_row_maps(m._linear_banks, m._kernel_banks, m.n_pairs)
                for m in self._members]
            self._step_plans = [
                _dag_step_plans(m._linear_banks, m._kernel_banks,
                                m.n_classes)
                for m in self._members]

        self._forward_jit = jax.jit(self._forward)
        # Serving hot path: labels only, model_idx donated -> label buffer.
        self._labels_jit = jax.jit(self._labels, donate_argnums=(1,))
        # Data-parallel serving legs, one per mesh (DESIGN.md §12.1).
        self._sharded: dict = {}

    # -- introspection -------------------------------------------------------

    def member(self, model: ModelRef) -> CompiledMachine:
        return self._members[self.model_index(model)]

    def model_index(self, model: ModelRef) -> int:
        if isinstance(model, str):
            try:
                return self._index[model]
            except KeyError:
                raise KeyError(
                    f"unknown model id {model!r}; fleet serves "
                    f"{self.model_ids}") from None
        i = int(model)
        if not 0 <= i < self.n_models:
            raise IndexError(f"model index {i} out of range "
                             f"[0, {self.n_models})")
        return i

    def pair_slice(self, model: ModelRef) -> tuple[int, int]:
        """Column slice of this member in the ``(n, P_total)`` tensor."""
        return self._pair_slices[self.model_index(model)]

    def describe(self) -> str:
        parts = [f"FleetMachine({self.n_models} models, "
                 f"P_total={self.n_pairs_total}, d_max={self.n_features})"]
        for mid, m, (lo, hi) in zip(self.model_ids, self._members,
                                    self._pair_slices):
            parts.append(f"  [{mid}] cols {lo}:{hi} K={m.n_classes} "
                         f"P={m.n_pairs} d={m.n_features}")
        return "\n".join(parts)

    # -- the single co-batched forward --------------------------------------

    def _member_scores(self, i: int, x: jnp.ndarray) -> jnp.ndarray:
        """Member ``i``'s exact forward subgraph on its feature slice."""
        m = self._members[i]
        xm = x[:, : m.n_features] if m.n_features != x.shape[1] else x
        return _all_scores(xm, m._linear_banks, m._kernel_banks,
                           m._inv_perm, self.use_pallas,
                           interpret=self.interpret)

    def _forward(self, x: jnp.ndarray, model_idx: jnp.ndarray):
        """x (n, d_max), model_idx (n,) -> (labels (n,), scores (n, P_tot))."""
        cols, labels = [], []
        for i in range(self.n_models):
            scores = self._member_scores(i, x)
            bits = (scores >= 0.0).astype(jnp.int32)
            labels.append(self._deciders[i](bits).astype(jnp.int32))
            cols.append(scores)
        lab = jnp.stack(labels, axis=0)                      # (M, n)
        routed = jnp.take_along_axis(
            lab, model_idx[None, :].astype(jnp.int32), axis=0)[0]
        return routed, jnp.concatenate(cols, axis=1)

    def _labels(self, x: jnp.ndarray, model_idx: jnp.ndarray) -> jnp.ndarray:
        """Serving hot path: routed labels only.

        ``decider="votes"``: the forward's scores concat is DCE'd, labels
        come from the dense per-member decision encoders.  ``"dag"``:
        each member runs its K-1-step elimination front — O(n*K) pair
        evaluations per member instead of O(n*K^2).
        """
        if self.decider == "dag":
            labels = []
            for i, m in enumerate(self._members):
                xm = x[:, : m.n_features] \
                    if m.n_features != x.shape[1] else x
                labels.append(_dag_labels(
                    xm, m.n_classes, self._pair_matrices[i],
                    m._linear_banks, m._kernel_banks,
                    self._row_maps[i], self._step_plans[i]).astype(jnp.int32))
            lab = jnp.stack(labels, axis=0)                  # (M, n)
            return jnp.take_along_axis(
                lab, model_idx[None, :].astype(jnp.int32), axis=0)[0]
        return self._forward(x, model_idx)[0]

    # -- data-parallel serving leg (DESIGN.md §12.1) -------------------------

    def shard(self, mesh) -> "ShardedFleetForward":
        """The mesh-sharded labels program for ``mesh`` (cached per mesh).

        ``mesh`` is a 1-D ``launch.mesh.make_serving_mesh`` mesh; the
        returned :class:`ShardedFleetForward` runs this fleet's exact
        ``_labels`` program on each device's row slice (banks replicated,
        batch axis sharded, no collectives), so every per-device slice is
        bit-identical to the single-device forward on the same rows.
        """
        fwd = self._sharded.get(mesh)
        if fwd is None:
            fwd = ShardedFleetForward(self, mesh)
            self._sharded[mesh] = fwd
        return fwd

    # -- host API ------------------------------------------------------------

    def _pad_features(self, x: np.ndarray) -> np.ndarray:
        x = np.asarray(x, np.float32)
        if x.ndim != 2 or x.shape[1] > self.n_features:
            raise ValueError(
                f"expected (n, <= {self.n_features}) inputs, got {x.shape}")
        if x.shape[1] < self.n_features:
            x = np.pad(x, ((0, 0), (0, self.n_features - x.shape[1])))
        return x

    def _resolve_idx(self, model, n: int) -> np.ndarray:
        if isinstance(model, (str, int, np.integer)):
            return np.full((n,), self.model_index(model), np.int32)
        idx = np.asarray([self.model_index(m) for m in model], np.int32)
        if idx.shape != (n,):
            raise ValueError(f"{idx.shape[0]} model ids for {n} rows")
        return idx

    def _run(self, x: np.ndarray, model):
        x = self._pad_features(x)
        idx = self._resolve_idx(model, x.shape[0])
        return self._forward_jit(jnp.asarray(x), jnp.asarray(idx))

    def predict(self, x: np.ndarray, model) -> np.ndarray:
        """Routed class labels (n,) via the compiled decision front.
        ``model`` is one id (str/int) for the whole batch or a per-row
        sequence of ids."""
        if self.decider == "dag":
            x = self._pad_features(x)
            idx = self._resolve_idx(model, x.shape[0])
            return np.asarray(
                self._labels_jit(jnp.asarray(x), jnp.asarray(idx)))
        return np.asarray(self._run(x, model)[0])

    def predict_votes(self, x: np.ndarray, model) -> np.ndarray:
        """Routed labels via the dense votes oracle, regardless of the
        compiled ``decider``."""
        return np.asarray(self._run(x, model)[0])

    def decision_scores(self, x: np.ndarray, model: ModelRef) -> np.ndarray:
        """ONE member's raw pair scores (n, P_m) out of the co-batched
        forward — the bit-identity probe against ``member.decision_scores``.
        """
        lo, hi = self.pair_slice(model)
        return np.asarray(self._run(x, model)[1][:, lo:hi])

    def predict_bits(self, x: np.ndarray, model: ModelRef) -> np.ndarray:
        """ONE member's comparator bits (n, P_m) from the co-batched pass."""
        return (self.decision_scores(x, model) >= 0.0).astype(np.int32)

    def accuracy(self, x: np.ndarray, y: np.ndarray, model: ModelRef) -> float:
        return float(np.mean(self.predict(x, model) == np.asarray(y)))

    # -- serialization (one npz + json for the whole fleet) ------------------

    def save(self, path: str) -> None:
        """Write ``<path>.npz`` + ``<path>.json`` packing every member."""
        path = _strip_ext(path)
        arrays: dict[str, np.ndarray] = {}
        members = []
        for i, (mid, m) in enumerate(zip(self.model_ids, self._members)):
            arr, meta_banks = _bank_arrays(
                m._linear_banks, m._kernel_banks, prefix=f"m{i}.")
            arrays.update(arr)
            members.append({"model_id": mid, "n_classes": m.n_classes,
                            "kernel_map": m.kernel_map, "banks": meta_banks})
        meta = {"format": _FLEET_FORMAT, "version": _FLEET_VERSION,
                "decider": self.decider, "members": members}
        np.savez(path + ".npz", **arrays)
        with open(path + ".json", "w") as f:
            json.dump(meta, f, indent=2)

    @classmethod
    def load(cls, path: str, use_pallas: Optional[bool] = None,
             interpret: Optional[bool] = None,
             decider: Optional[str] = None) -> "FleetMachine":
        path = _strip_ext(path)
        with open(path + ".json") as f:
            meta = json.load(f)
        if meta.get("format") != _FLEET_FORMAT:
            raise ValueError(f"{path}.json is not a FleetMachine save")
        npz = np.load(path + ".npz")
        ids, machines = [], []
        for entry in meta["members"]:
            linear_banks, kernel_banks = _banks_from_entries(
                entry["banks"], npz)
            ids.append(entry["model_id"])
            machines.append(CompiledMachine(
                entry["n_classes"], linear_banks, kernel_banks,
                kernel_map=entry.get("kernel_map"), use_pallas=use_pallas,
                interpret=interpret))
        if decider is None:
            decider = meta.get("decider", "votes")
        return cls(ids, machines, use_pallas=use_pallas, interpret=interpret,
                   decider=decider)


class ShardedFleetForward:
    """Data-parallel fleet labels over a ``make_serving_mesh`` (DESIGN.md §12.1).

    ``shard_map`` splits the ``(n, d_max)`` batch across the mesh's
    ``"batch"`` axis; banks are replicated (they are closed-over
    constants of the member subgraphs) and there are NO collectives, so
    each device executes the *identical* single-device ``_labels``
    program on its ``n / n_devices`` row slice — the PR 7 bit-identity
    contract extends per shard.  The jit keeps the serving hot path's
    donation: ``model_idx`` (i32 ``(n,)``) is donated and reused for the
    label output, verified by the analyzer on a 1-device mesh
    (``FleetMachine._labels[sharded]`` entry point).

    Callers pass HOST numpy arrays whose row count is a multiple of
    ``n_devices`` (the engine rounds buckets to whole per-device slices
    and validity-masks the tail padding); jit commits them straight to
    the sharded layout — no per-dispatch ``device_put`` round trip.
    """

    def __init__(self, fleet: FleetMachine, mesh):
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.launch.mesh import SERVING_AXIS

        if tuple(mesh.axis_names) != (SERVING_AXIS,):
            raise ValueError(
                f"serving mesh needs the 1-D axis ({SERVING_AXIS!r},) "
                f"(launch.mesh.make_serving_mesh); got {mesh.axis_names}")
        self.fleet = fleet
        self.mesh = mesh
        self.n_devices = int(mesh.shape[SERVING_AXIS])
        spec = PartitionSpec(SERVING_AXIS)
        self._sharding = NamedSharding(mesh, spec)
        # check_vma off: Pallas banks (use_pallas, as on the TPU) declare
        # their outputs without a varying-mesh-axes annotation.
        self._labels_jit = jax.jit(
            jax.shard_map(fleet._labels, mesh=mesh, in_specs=(spec, spec),
                          out_specs=spec, check_vma=False),
            in_shardings=(self._sharding, self._sharding),
            out_shardings=self._sharding,
            donate_argnums=(1,))

    def global_rows(self, per_device_rows: int) -> int:
        """Whole-slice rounding: the global batch for one device bucket."""
        return int(per_device_rows) * self.n_devices

    def __call__(self, x, model_idx) -> jnp.ndarray:
        """Async sharded dispatch; rows must divide evenly over devices."""
        n = x.shape[0]
        if n % self.n_devices:
            raise ValueError(
                f"{n} rows not divisible into {self.n_devices} device "
                f"slices; pad to whole per-device slices first")
        return self._labels_jit(x, model_idx)

    def predict(self, x: np.ndarray, model) -> np.ndarray:
        """Blocking convenience wrapper: pads the tail to whole per-device
        slices (zeros, model 0 — computed and discarded), trims on return."""
        x = self.fleet._pad_features(x)
        idx = self.fleet._resolve_idx(model, x.shape[0])
        n = x.shape[0]
        pad = -n % self.n_devices
        if pad:
            x = np.pad(x, ((0, pad), (0, 0)))
            idx = np.pad(idx, (0, pad))
        return np.asarray(self(x, idx))[:n]


def compile_fleet(
    machines: Union[Mapping[str, CompiledMachine],
                    Sequence[tuple[str, CompiledMachine]],
                    Sequence[CompiledMachine]],
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    decider: str = "votes",
) -> FleetMachine:
    """Concatenate compiled machines into one co-batched :class:`FleetMachine`.

    ``machines`` is a ``{model_id: CompiledMachine}`` mapping (insertion
    order fixes the member order), a sequence of ``(model_id, machine)``
    pairs, or a bare sequence of machines (ids default to ``"model<i>"``).
    A single-member fleet is valid — it is how the serving engine wraps a
    lone :class:`CompiledMachine`.
    """
    if isinstance(machines, Mapping):
        items = list(machines.items())
    else:
        items = []
        for i, it in enumerate(machines):
            if isinstance(it, tuple) and len(it) == 2:
                items.append((str(it[0]), it[1]))
            else:
                items.append((f"model{i}", it))
    ids = [i for i, _ in items]
    members = [m for _, m in items]
    for m in members:
        if not isinstance(m, CompiledMachine):
            raise TypeError(
                f"compile_fleet takes CompiledMachine members, got "
                f"{type(m).__name__}; lower with compile_machine first")
    return FleetMachine(ids, members, use_pallas=use_pallas,
                        interpret=interpret, decider=decider)
