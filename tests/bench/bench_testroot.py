"""A throwaway benchmark root for the CPU tests: the repository's ``bench/``
copied into a temporary directory, with a ``BENCHMARK.json`` of tiny cells
(a 2-layer dense GQA model served through the Engine, and fd2d on 256^2).
The cells use the real checks and limits of the configurations they stand
in for, so a sound run is correct and a broken one is not."""

from __future__ import annotations

import contextlib
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY = {
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "serving": {"batch": 4, "max_len": 256},
}
MIXES = {
    "tchat": {"loop": "open", "rate_per_s": 6.0,
              "prompt": {"dist": "lognormal", "median": 48, "sigma": 0.8,
                         "min": 16, "max": 128, "grid": 32},
              "output": {"dist": "lognormal", "median": 32, "sigma": 0.5,
                         "min": 16, "max": 64}},
    "toff": {"loop": "closed", "outstanding": 8, "pool": 64, "block": 8,
             "prompt": {"dist": "uniform", "min": 32, "max": 128,
                        "grid": 32},
             "output": {"dist": "uniform", "min": 16, "max": 48}},
}


def make_root(tmp: Path) -> Path:
    """The tiny benchmark under ``tmp``; returns its root."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    lm = json.loads((REPO / "bench/configs/internlm2-1.8b.json").read_text())
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(dict(lm,
                                                                 **TINY)))
    fd = json.loads((REPO / "bench/configs/fd2d-8192.json").read_text())
    (tmp / "bench/configs/fdtiny.json").write_text(
        json.dumps(dict(fd, width=256, height=256)))
    for name, mix in MIXES.items():
        (tmp / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
    for new, old in (("tiny", "internlm2-1.8b"), ("fdtiny", "fd2d-8192")):
        shutil.copy(tmp / f"bench/check/{old}.py",
                    tmp / f"bench/check/{new}.py")
    spec["configs"] = [
        {"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
         "reduced": [], "why": "test"},
        {"name": "fdtiny", "source": "test",
         "file": "bench/configs/fdtiny.json", "reduced": [], "why": "test"}]
    cells = {"internlm2-1.8b.chat": ("tiny.chat", "tiny", "tchat"),
             "internlm2-1.8b.offline": ("tiny.offline", "tiny", "toff"),
             "fd2d-8192.wave": ("fdtiny.wave", "fdtiny", "wave")}
    spec["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                          "why": "test"} for n, c, t in cells.values()]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cells[w][0] for w in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@contextlib.contextmanager
def cpu_run(root: Path):
    """The chip gate's stand-ins for a run under ``root`` on the CPU, with
    the persistent compile cache off; JAX's settings are restored
    afterwards."""
    import jax

    from repro.launch import compile_cache

    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    old_dir = compile_cache.CHECKOUT_CACHE
    compile_cache.CHECKOUT_CACHE = root / ".jax_cache"
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield {"root": root, "devices": jax.devices()[:1],
               "peaks_kind": "TPU v5 lite"}
    finally:
        compile_cache.CHECKOUT_CACHE = old_dir
        for k, v in keep.items():
            jax.config.update(k, v)


def run_cell(root: Path, workload: str, *, seed: int = 2 ** 31 + 7,
             seconds: float = 4.0) -> dict:
    """One run of a cell on the CPU, as ``bench/run.py`` makes it."""
    from bench import run

    with cpu_run(root) as stand_ins:
        return run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0"],
                        **stand_ins)
