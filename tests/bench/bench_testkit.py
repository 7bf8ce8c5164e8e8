"""A checkout of the benchmark in a temporary directory with a tiny
configuration and a tiny cell added as new files and entries, the way a
later change adds them; runs a cell there on the CPU."""
from __future__ import annotations

import argparse
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

def make_root(tmp: Path) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    (root / "src").symlink_to(ROOT / "src")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": ["n"], "why": "tiny"})
    cfg = json.loads((ROOT / "bench/configs/mso-n1024.json").read_text())
    cfg["model"]["n"] = 64
    cfg["fit"]["train_steps"] = 600
    cfg["signal_steps"] = 4000
    cfg["engine"].update(max_slots=8, max_wave=2)
    _write(root / "bench/configs/tiny.json", cfg)
    cell, base = "tiny.forecast", "mso-n1024.forecast"
    bench["workloads"].append({"name": cell, "config": "tiny",
                               "traffic": "forecast", "chips": 1,
                               "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if base in m.get("workloads", ()):
            m["workloads"].append(cell)
    wl = json.loads((ROOT / f"bench/workloads/{base}.json").read_text())
    wl.update(config="tiny", run_in_s=0.5, grace_s=30.0, check_requests=4,
              limits={"out_gap": 1e-4})
    wl["traffic"].update(rate=6.0, prompt={"xm": 16, "alpha": 1.3, "cap": 40},
                         horizon={"lo": 8, "hi": 20})
    _write(root / f"bench/workloads/{cell}.json", wl)
    _write(root / "BENCHMARK.json", bench)
    peaks = json.loads((root / "bench/peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_per_s": 1e11, "hbm_bytes_per_s": 1e10}
    _write(root / "bench/peaks.json", peaks)
    return root


def _write(path: Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))


def run(root: Path, cell: str, *, seed: int = 2**33 + 7, seconds: float = 2.0,
        trace: int = 0, fault=None) -> dict:
    import os

    import jax
    from bench import run as run_mod
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace)
    # The run turns on the compile cache in its checkout; the rest of the
    # test process keeps its own settings.
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    try:
        return run_mod.run_cell(args, root=root, require_tpu=False,
                                fault=fault)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        if env is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env
