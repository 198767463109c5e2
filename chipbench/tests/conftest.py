"""The benchmark's own tests run on the CPU, with no persistent compile
cache, apart from the repository's tier-1 suite under ``tests/``."""
import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402


def cpu_numerics(cfg: dict) -> dict:
    """``cfg`` with the numerics the program has on the CPU, where its
    matrix products take float32 operands."""
    cfg["numerics"] = {**cfg["numerics"], "matmul_operands": "float32",
                       "fused_chain_operands": "float32"}
    return cfg


def tiny_bench(tmp_path: Path, res: int) -> Path:
    """A copy of ``BENCHMARK.json`` whose configurations serve ``res`` x
    ``res`` images, at the numerics of the CPU; everything else as
    committed."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for conf in bench["configs"]:
        cfg = cpu_numerics(json.loads((ROOT / conf["file"]).read_text()))
        cfg["resolution"] = res
        cfg["serve"]["res"] = [res, res]
        path = tmp_path / Path(conf["file"]).name
        path.write_text(json.dumps(cfg))
        conf["file"] = str(path)
    out = tmp_path / "BENCHMARK.json"
    out.write_text(json.dumps(bench))
    return out


@pytest.fixture
def rehearse(tmp_path, monkeypatch, capsys):
    """Run one cell through ``run.main`` on the CPU at a tiny size, past
    the look for a TPU, with the Pallas kernels in interpret mode; returns
    (exit code, the last line of stdout as JSON or None)."""
    import jax

    import repro.core.executor as executor
    from chipbench import run

    monkeypatch.setattr(executor, "_default_use_pallas", lambda: True)
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    monkeypatch.setattr(run, "peak_of", lambda kind: peaks["TPU v5 lite"])
    bench = tiny_bench(tmp_path, 64)

    def go(workload, *, seed=2**31 + 11, seconds=2, trace=0):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      bench_path=bench,
                      require=lambda chips: jax.devices()[:chips])
        lines = capsys.readouterr().out.strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None)
    return go
