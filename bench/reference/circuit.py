"""Plain reference of a deployed mixed-kernel mixed-signal OvO machine.

Written from the paper's equations and the configuration's data alone; it
imports nothing of the system under test.  What it reads:

* the fitted pair models (``<config>_fit.npz`` / ``.json``): support
  vectors, dual coefficients, biases and widths of every one-vs-one pair,
  and which kernel Algorithm 1 chose for it;
* the circuit parameters and deployment settings in the configuration
  file (``circuit``, ``deploy``).

From those it rebuilds, in plain NumPy:

1. the analog core's behavioural model: the DC sweeps of the Gaussian cell
   and of the alpha multiplier under the calibration instance's mismatch,
   the weighted least-squares Gaussian fit (Eq. 7) and the logistic fit
   (Eq. 9);
2. the deployed classifiers: analog RBF pairs (alpha pruning, alpha
   normalisation, the control-voltage mapping, the realised alpha read off
   the measured sweep, signed rails, comparator offset) and digital linear
   pairs (symmetric fixed-point weights, 4-bit ADC inputs);
3. Monte-Carlo variants: per-cell mismatch draws keyed by
   ``fold_in(split(key, P)[pair], variant)``, reduced to a bell shift, a
   peak gain, an alpha control shift and slope and a comparator offset;
4. the votes decision (lowest class wins a tie), per-variant accuracy,
   and the folded statistics (mean, worst, best, variants in spec).

``dtype`` sets the precision of every arithmetic step.  The reference runs
in float64; its lower-precision control runs the same code in bfloat16.
Random draws come from ``jax.random`` on the host CPU (the draws are
part of the key's definition, not of the system).
"""
from __future__ import annotations

import itertools
import json
import os

import numpy as np

#: Mismatch draws per Gaussian cell (two pair thresholds, mirror ratio,
#: V_DS modulation) and per alpha multiplier (threshold, slope).
N_GAUSS = 4
N_ALPHA = 2


def _cpu():
    import jax

    return jax.devices("cpu")[0]


class Machine:
    """One configuration's fitted pairs, deployed as its ``circuit`` target.

    ``cfg`` is the parsed configuration file; ``root`` the directory that
    holds its data files.
    """

    def __init__(self, cfg: dict, root: str):
        data = cfg["data"]
        with open(os.path.join(root, data["fit"] + ".json")) as f:
            meta = json.load(f)
        npz = np.load(os.path.join(root, data["fit"] + ".npz"))
        self.k = int(meta["n_classes"])
        self.pairs = list(itertools.combinations(range(self.k), 2))
        dep = cfg["deploy"]
        self.weight_bits = int(dep["weight_bits"])
        self.input_bits = int(dep["input_bits"])
        self.alpha_floor_rel = float(dep["alpha_floor_rel"])
        self.hw_seed = int(dep["hw_seed"])
        self.circuit = {k: float(v) for k, v in cfg["circuit"].items()}
        self.kernel_map = [e["kernel"] for e in meta["pairs"]]
        if len(self.kernel_map) != len(self.pairs):
            raise ValueError("fit file pair count does not match K")

        def model(i, slot):
            m = meta["pairs"][i]["models"][slot]
            get = lambda n: npz[f"p{i}.{slot}.{n}"]  # noqa: E731
            return dict(kind=m["kind"], bias=float(m["bias"]),
                        gamma=float(m["gamma"]),
                        w=get("w") if m["has_w"] else None,
                        sx=get("support_x") if not m["has_w"] else None,
                        sy=get("support_y") if not m["has_w"] else None,
                        alpha=get("alpha") if not m["has_w"] else None)

        self.linear = [model(i, "model_linear") for i in range(len(self.pairs))]
        self.hw_models = [model(i, "model_hw") for i in range(len(self.pairs))]
        self._calibrate()
        self.analog = [self._deploy_analog(m) for m in self.hw_models]
        #: Padded slot count of the analog candidate bank: variant draws are
        #: taken at this shape for every pair and cut to the pair's cells.
        self.m_max = max(a["m"] for a in self.analog)
        self.digital = [self._deploy_linear(m) for m in self.linear]
        self._draw = None

    # -- behavioural model of the analog core (Sec. IV-A) -------------------

    def _calibrate(self) -> None:
        core = calibrate(self.circuit, self.hw_seed)
        self.mu, self.gamma0 = core["mu"], core["gamma0"]
        self.dv_grid, self.curve = core["dv_grid"], core["curve"]
        self.alpha_x0, self.alpha_s = core["alpha_x0"], core["alpha_s"]
        self.dva_grid, self.alpha_curve = core["dva_grid"], core["alpha_curve"]

    def _deploy_analog(self, m: dict) -> dict:
        alpha = np.asarray(m["alpha"], np.float64)
        amax = float(alpha.max()) if alpha.size else 1.0
        keep = np.flatnonzero(alpha >= self.alpha_floor_rel * amax)
        scale = amax * 1.05
        a_hw = np.clip(alpha[keep] / scale, 1e-4, 1.0 - 1e-4)
        v_scale = self.circuit["v_scale"]
        return dict(
            m=len(keep), sv=np.asarray(m["sx"], np.float64)[keep],
            pos=np.asarray(m["sy"])[keep] > 0,
            dva=self.alpha_x0 + self.alpha_s * np.log(1.0 / a_hw - 1.0),
            bias=m["bias"] / scale,
            # Eq. 8: s = sqrt(gamma* / gamma0) in volt units, times v_scale.
            scale=v_scale * np.sqrt(m["gamma"] / (self.gamma0 * v_scale ** 2)))

    def _deploy_linear(self, m: dict) -> dict:
        wb = np.concatenate([np.asarray(m["w"], np.float64), [m["bias"]]])
        bits = self.weight_bits
        qmax = (1 << (bits - 1)) - 1
        amax = float(np.max(np.abs(wb)))
        frac = bits - 1 if amax <= 0 else int(np.clip(
            np.floor(np.log2(qmax / amax) + 1e-9), -(126 - bits), 126))
        step = 2.0 ** (-frac)
        q = np.clip(np.round(wb / step), -qmax, qmax) * step
        return dict(w=q[:-1], b=q[-1])

    # -- scoring -------------------------------------------------------------

    def draws(self, key_seed: int, pair: int, variants) -> dict:
        """Mismatch draws of ``pair``'s analog candidate for the given
        global variant indices, cut from the bank's padded slot grid."""
        import jax

        p = len(self.pairs)
        d = self.analog[pair]["sv"].shape[1]
        if self._draw is None:
            def one(pk, v):
                kg, ka, kc = jax.random.split(jax.random.fold_in(pk, v), 3)
                return (jax.random.normal(kg, (self.m_max, d, N_GAUSS)),
                        jax.random.normal(ka, (self.m_max, N_ALPHA)),
                        jax.random.normal(kc, ()))

            self._draw = jax.jit(jax.vmap(one, in_axes=(None, 0)))
        with jax.default_device(_cpu()):
            pk = jax.random.split(jax.random.PRNGKey(key_seed), p)[pair]
            g, a, c = self._draw(pk, np.asarray(variants, np.int32))
        m = self.analog[pair]["m"]
        return dict(g=np.asarray(g, np.float64)[:, :m],
                    a=np.asarray(a, np.float64)[:, :m],
                    c=np.asarray(c, np.float64))

    def analog_scores(self, x: np.ndarray, pair: int, draws=None,
                      dtype=np.float64, rows: int = 512) -> np.ndarray:
        """Comparator input of ``pair``'s analog classifier: ``(V, n)``
        under ``draws``, or ``(1, n)`` for the nominal circuit."""
        c = self.circuit
        an = self.analog[pair]
        t = lambda v: np.asarray(v, np.float64).astype(dtype)  # noqa: E731
        m, d = an["sv"].shape
        if draws is None:
            shift = np.zeros((1, m, d))
            gain = np.ones((1, m, d))
            a_shift, a_slope, comp = np.zeros((1, m)), np.ones((1, m)), \
                np.zeros(1)
        else:
            g, a = draws["g"], draws["a"]
            nvt = c["n"] * c["v_t"]
            shift = 0.5 * (g[..., 0] + g[..., 1]) * c["sigma_vth"]
            diff = 0.5 * (g[..., 0] - g[..., 1]) * (c["sigma_vth"] / nvt)
            peak = 4.0 / (1.0 + np.exp(diff)) * (1.0 - 1.0 / (1.0 + np.exp(-diff)))
            gain = (peak * (1.0 + g[..., 2] * c["mirror_err"])
                    * (1.0 + g[..., 3] * c["lambda_ds"]))
            a_shift = a[..., 0] * c["sigma_vth"]
            a_slope = 1.0 + a[..., 1] * 0.02
            comp = draws["c"] * (c["comparator_sigma"] / c["i_bias"])
        comp = comp + c["comparator_offset"] / c["i_bias"]
        q = (an["dva"][None] - a_shift) / a_slope
        alpha = np.interp(q, self.dva_grid, self.alpha_curve)       # (V, m)
        grid, curve = t(self.dv_grid), t(self.curve)
        sv, sc, mu = t(an["sv"]), t(an["scale"]), t(self.mu)
        shift, gain = t(shift), t(gain)
        coef_p = t(alpha * an["pos"][None])
        coef_n = t(alpha * ~an["pos"][None])
        out = []
        for lo in range(0, len(x), rows):
            xb = t(x[lo:lo + rows])
            k = None
            for j in range(d):
                dv = sc * (xb[:, None, j] - sv[None, :, j]) + mu       # (n, m)
                dv = dv[None] - shift[:, None, :, j]                  # (V, n, m)
                cell = _interp(dv, grid, curve).astype(dtype)
                cell = cell * gain[:, None, :, j]
                k = cell if k is None else k * cell
            ip = np.sum(k * coef_p[:, None], axis=-1, dtype=dtype) \
                + t(max(an["bias"], 0.0))
            im = np.sum(k * coef_n[:, None], axis=-1, dtype=dtype) \
                + t(max(-an["bias"], 0.0))
            out.append((ip - im).astype(np.float64))
        return np.concatenate(out, axis=1) + comp[:, None]

    def linear_scores(self, x: np.ndarray, pair: int,
                      dtype=np.float64) -> np.ndarray:
        """Decision value of ``pair``'s digital linear classifier: ``(n,)``."""
        lv = (1 << self.input_bits) - 1
        xq = np.round(np.clip(np.asarray(x, np.float64), 0.0, 1.0) * lv) / lv
        dg = self.digital[pair]
        s = xq.astype(dtype) @ dg["w"].astype(dtype) + dtype(dg["b"])
        return np.asarray(s, np.float64)

    def scores(self, x: np.ndarray, key_seed=None, variants=(),
               dtype=np.float64) -> np.ndarray:
        """Deciding scores of the circuit assignment: ``(V, n, P)``.

        With ``key_seed`` the analog pairs are the given Monte-Carlo
        variants; without it, the nominal circuit (``V = 1``).
        """
        nv = len(variants) if key_seed is not None else 1
        out = np.zeros((nv, len(x), len(self.pairs)))
        for p, kind in enumerate(self.kernel_map):
            if kind == "rbf":
                dr = None if key_seed is None else \
                    self.draws(key_seed, p, variants)
                out[:, :, p] = self.analog_scores(x, p, dr, dtype)
            else:
                out[:, :, p] = self.linear_scores(x, p, dtype)[None]
        return out

    def ambiguous(self, scores: np.ndarray, tie_eps: dict) -> np.ndarray:
        """Rows whose label a comparator tie can change: ``(..., n)`` bool.

        A pair's bit is a tie where its score lies within ``tie_eps[kind]``
        of 0 (the kind of its circuit target, ``linear`` or ``rbf``).  A
        row is ambiguous if flipping any one such bit, or all of them,
        changes its label."""
        eps = np.array([tie_eps[k] for k in self.kernel_map])
        band = np.abs(scores) <= eps
        lab = self.labels(scores)
        flipped = np.where(scores >= 0.0, -1.0, 1.0)
        amb = self.labels(np.where(band, flipped, scores)) != lab
        for p in np.flatnonzero(band.any(axis=tuple(range(band.ndim - 1)))):
            one = scores.copy()
            one[..., p] = np.where(band[..., p], flipped[..., p],
                                   scores[..., p])
            amb |= self.labels(one) != lab
        return amb

    def labels(self, scores: np.ndarray) -> np.ndarray:
        """Votes decision over ``(..., P)`` scores (bit = score >= 0)."""
        bits = (scores >= 0.0).astype(np.int64)
        votes = np.zeros(scores.shape[:-1] + (self.k,), np.int64)
        for p, (i, j) in enumerate(self.pairs):
            votes[..., i] += bits[..., p]
            votes[..., j] += 1 - bits[..., p]
        return np.argmax(votes, axis=-1)


def calibrate(c: dict, hw_seed: int) -> dict:
    """The analog core's behavioural model (Sec. IV-A) from its circuit
    parameters ``c`` and the calibration instance's mismatch seed."""
    import jax

    with jax.default_device(_cpu()):
        kg, ka = jax.random.split(jax.random.PRNGKey(hw_seed))
        og = np.asarray(jax.random.normal(kg, (N_GAUSS,)), np.float64)
        oa = np.asarray(jax.random.normal(ka, (N_ALPHA,)), np.float64)
    nvt = c["n"] * c["v_t"]
    dv = np.linspace(-c["v_range"], c["v_range"], 257)
    dvc = np.clip(dv, -c["v_range"], c["v_range"])
    f1 = 1.0 / (1.0 + np.exp(-(dvc - og[0] * c["sigma_vth"]) / nvt))
    f2 = 1.0 - 1.0 / (1.0 + np.exp(-(dvc - og[1] * c["sigma_vth"]) / nvt))
    out = (f1 * f2 * (1.0 + og[2] * c["mirror_err"])
           * (1.0 + og[3] * c["lambda_ds"]))
    # Eq. 7: log I = a + b dv + c dv^2, weighted by I^2.
    i = np.clip(out, 1e-12, None)
    w = i * i
    basis = np.stack([np.ones_like(dv), dv, dv * dv], axis=1)
    wb = basis * w[:, None]
    _, b_, c_ = np.linalg.solve(basis.T @ wb, wb.T @ np.log(i))
    gamma0 = max(-c_, 1e-9)
    # Eq. 9: dV_alpha = x0 + s ln(1/alpha - 1), fitted to the sweep.
    dva = np.linspace(-0.25, 0.25, 257)
    nvt_a = nvt * (1.0 + oa[1] * 0.02)
    ratio = 1.0 / (1.0 + np.exp((dva - oa[0] * c["sigma_vth"]) / nvt_a))
    keep = (ratio > 1e-4) & (ratio < 1.0 - 1e-4)
    s, x0 = np.polyfit(np.log(1.0 / ratio[keep] - 1.0), dva[keep], 1)
    order = np.argsort(dva)
    return dict(mu=b_ / (2.0 * gamma0), gamma0=gamma0, dv_grid=dv,
                curve=out / out.max(), alpha_x0=x0, alpha_s=s,
                dva_grid=dva[order], alpha_curve=ratio[order],
                v_scale=c["v_scale"], v_range=c["v_range"])


def _interp(v: np.ndarray, grid: np.ndarray, curve: np.ndarray) -> np.ndarray:
    """Piecewise-linear read of a measured curve, clamped at its ends."""
    flat = np.interp(np.asarray(v, np.float64).ravel(),
                     np.asarray(grid, np.float64), np.asarray(curve, np.float64))
    return flat.reshape(np.shape(v))


def fold(acc: np.ndarray, floor: float) -> dict:
    """Folded statistics of per-variant accuracies ``acc (V,)``."""
    acc = np.asarray(acc, np.float64)
    return dict(mean=float(acc.mean()), worst=float(acc.min()),
                best=float(acc.max()),
                in_spec=int(np.sum(acc >= np.float32(floor))))
