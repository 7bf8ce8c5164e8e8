"""Roofline terms per (arch x shape) from the dry-run artifacts (§Roofline).

Hardware model (TPU v5e): 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.

Sources: compiled.cost_analysis() gives per-device HLO FLOPs/bytes with
while-loop bodies counted ONCE (verified empirically) — the 2/4-unit unrolled
probes give the exact per-layer body cost, extrapolated to full depth:

    flops(L) = rest + L * body,   body = (P4 - P2) / (L4 - L2)

collective bytes come from parsing the optimized HLO (trip-count-adjusted).

The fused-decode section is self-contained (no dryrun artifact): it lowers
one fused K-token decode dispatch and reports achieved vs theoretical
bytes/token — see :func:`fused_decode_cost`.
"""
from __future__ import annotations

import json
import os

from repro.configs import REGISTRY, SHAPES

from . import _util

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link
DRYRUN = os.path.join(_util.ARTIFACTS, "dryrun.jsonl")


def load_records(path=DRYRUN):
    recs = {}
    probes = {}
    if not os.path.exists(path):
        return recs, probes
    with open(path) as f:
        for line in f:
            try:
                r = json.loads(line)
            except Exception:
                continue
            key = (r.get("arch"), r.get("shape"))
            if r.get("status") == "probe" or str(r.get("mesh", "")).startswith(
                    "probe"):
                if r.get("status") in ("probe", "ok"):
                    probes.setdefault(key, {})[r["probe_units"]] = r["cost"]
            elif r.get("status") in ("ok", "skipped", "error"):
                recs[(r["arch"], r["shape"], r["mesh"])] = r
    return recs, probes


def corrected_cost(rec, probes):
    """Per-device (flops, bytes) with scan-depth extrapolation via probes."""
    arch, shape = rec["arch"], rec["shape"]
    cfg = REGISTRY[arch]
    raw_f = rec["cost"]["flops"]
    raw_b = rec["cost"]["bytes_accessed"]
    pr = probes.get((arch, shape))
    if not pr or 2 not in pr or 4 not in pr:
        return raw_f, raw_b, "raw"
    pat = len(cfg.block_pattern)
    l2, l4 = 2 * pat, 4 * pat
    body_f = (pr[4]["flops"] - pr[2]["flops"]) / (l4 - l2)
    body_b = (pr[4]["bytes_accessed"] - pr[2]["bytes_accessed"]) / (l4 - l2)
    rest_f = pr[2]["flops"] - l2 * body_f
    rest_b = pr[2]["bytes_accessed"] - l2 * body_b
    f = rest_f + cfg.n_layers * body_f
    b = rest_b + cfg.n_layers * body_b
    # Guard: extrapolation must not undercut the raw report.
    return max(f, raw_f), max(b, raw_b), "probe-extrapolated"


def model_flops(cfg, cell):
    """6 * N_active * D (training) / 2 * N_active * D (inference)."""
    n = cfg.active_param_count()
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    mult = 6 if cell.kind == "train" else 2
    return mult * n * tokens


def roofline_row(rec, probes):
    cfg = REGISTRY[rec["arch"]]
    cell = SHAPES[rec["shape"]]
    n_dev = rec["n_devices"]
    f_dev, b_dev, basis = corrected_cost(rec, probes)
    coll = rec["collectives"]["total_bytes"]  # per-device program bytes
    t_compute = f_dev / PEAK_FLOPS
    t_memory = b_dev / HBM_BW
    t_coll = coll / ICI_BW
    mf = model_flops(cfg, cell)
    hlo_global = f_dev * n_dev
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    # roofline fraction: useful model flops vs what the dominant term allows
    ideal_s = mf / (n_dev * PEAK_FLOPS)
    frac = ideal_s / bound if bound > 0 else 0.0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "basis": basis,
        "compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll,
        "dominant": dominant.replace("_s", ""),
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "roofline_fraction": frac,
        "peak_gib_per_dev": rec["memory"]["peak_bytes"] / 2 ** 30,
    }


def fused_decode_cost(n=512, b=8, k=16, d=1, seed=0):
    """Achieved vs theoretical HBM bytes/token for the fused decode kernel.

    Builds a DPG reservoir at the requested decode shape, lowers ONE fused
    K-token dispatch (``core.dispatch.run_decode_fused`` — diag step +
    readout + ensemble reduce + feedback write in one kernel) and reads the
    compiled ``cost_analysis()`` bytes.  The theoretical floor is the
    streaming minimum: every weight operand read once per dispatch, slot
    state read + written once, K*B output tokens written once — the number
    the kernel approaches as K amortizes the weight traffic.  Reported
    ``bytes_ratio`` = theory / achieved (1.0 = at the roofline floor;
    the trajectory gate watches it so kernel regressions that re-materialize
    state or re-read weights show up as the ratio dropping).
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import dispatch as core_dispatch
    from repro.core import esn as esn_fn
    from repro.core.esn import ESNConfig

    cfg = ESNConfig(n=n, d_in=d, d_out=d, spectral_radius=0.95, leak=0.9,
                    input_scaling=0.5, ridge_alpha=1e-8, seed=seed)
    params = esn_fn.dpg_params(cfg, "noisy_golden", sigma=0.1)
    rng = np.random.default_rng(seed)
    sig = np.sin(0.2 * np.arange(1501)) + rng.normal(0, 0.05, 1501)
    w_out = esn_fn.fit_host(params, sig[:-1, None], sig[1:, None],
                            washout=100).w_out
    use_fb = params.cfg.use_feedback
    w_drive = params.win_q + params.wfb_q if use_fb else params.win_q
    dt = params.lam_q.dtype
    states = jnp.zeros((b, params.lam_q.shape[-1]), dt)
    y_prev = jnp.zeros((b, d), dt)
    mask = jnp.ones((b,), bool)
    fn = jax.jit(functools.partial(
        core_dispatch.run_decode_fused, use_bias=params.cfg.use_bias,
        use_feedback=use_fb, ensemble="off"), static_argnums=(1, 7))
    comp = fn.lower(params.lam_q, params.n_real, w_drive, w_out,
                    states, y_prev, mask, k).compile()
    ca = comp.cost_analysis()
    if isinstance(ca, (list, tuple)):          # older jax: list of dicts
        ca = ca[0] if ca else {}
    achieved = float((ca or {}).get("bytes accessed", float("nan")))
    weight_b = (params.lam_q.size + w_drive.size + w_out.size) * dt.itemsize
    state_b = (states.size + y_prev.size) * dt.itemsize + mask.size
    theory = weight_b + 2 * state_b + k * b * d * dt.itemsize
    tokens = k * b
    ratio = theory / achieved if achieved == achieved and achieved > 0 \
        else float("nan")
    return {"bytes_per_token_theory": theory / tokens,
            "bytes_per_token_achieved": achieved / tokens,
            "bytes_ratio": ratio,
            "fused_flops_per_token":
                float((ca or {}).get("flops", float("nan"))) / tokens}


def main(quick=False):
    recs, probes = load_records()
    rows = []
    table = []
    for key, rec in sorted(recs.items()):
        if rec.get("status") != "ok" or rec.get("mesh") != "single":
            continue
        row = roofline_row(rec, probes)
        table.append(row)
        rows.append(_util.csv_row(
            f"roofline.{row['arch']}.{row['shape']}",
            row[row["dominant"] + "_s"] * 1e6,
            f"dominant={row['dominant']};frac={row['roofline_fraction']:.3f};"
            f"useful={row['useful_ratio']:.2f}"))
    # Fused-decode roofline needs no dryrun artifact: it lowers the serving
    # kernel itself, so the achieved-vs-theoretical ratio is always reported.
    n, b, k = (256, 4, 8) if quick else (512, 8, 16)
    fused = {"arch": "reservoir", "shape": f"decode_fused.n{n}.b{b}.k{k}",
             **fused_decode_cost(n=n, b=b, k=k)}
    table.append(fused)
    rows.append(_util.csv_row(
        f"roofline.decode_fused", fused["bytes_per_token_achieved"],
        f"theory_B_tok={fused['bytes_per_token_theory']:.0f};"
        f"ratio={fused['bytes_ratio']:.3f}"))
    _util.save_artifact("roofline.json", table)
    if len(rows) == 1:
        rows.append(_util.csv_row("roofline.pending", 0.0,
                                  "run repro.launch.dryrun for the arch rows"))
    return rows


if __name__ == "__main__":
    for r in main():
        print(r)
