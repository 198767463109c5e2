"""CPU rehearsal: the whole command at a tiny size, through the generator
process, to the last line; and the run refused without a TPU."""
import json
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def cell_metrics(section: str, cell: str) -> set:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {m["name"] for m in bench[section]
            if cell in m.get("workloads", [cell])}


def test_stream_cell_end_to_end(rehearse):
    rc, line = rehearse("mbv2.stream")
    assert rc == 0
    assert list(line) == KEYS + ["checks"]
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == cell_metrics("end_to_end", "mbv2.stream")
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["window_compiles"]["value"] == 0


def test_saturate_cell_traced(rehearse):
    rc, line = rehearse("shfl.saturate", trace=1)
    assert rc == 0
    assert list(line) == KEYS + ["breakdown", "checks"]
    assert line["correct"] is True, line["checks"]
    # the CPU has no device planes: the trace's metrics stay out of the
    # line, the counters' metrics do not
    assert {"mean_batch.saturate", "pad_share.saturate"} <= set(
        line["metrics"]) <= cell_metrics("per_layer", "shfl.saturate")
    assert {"busy_s", "window_s"} <= set(line["device"])


@pytest.mark.parametrize("fault,alter,check", [
    # every answer off by a fifth, the same for every copy: only the
    # reference can tell
    ("scaled", lambda out: out * 1.2, "ref_rel_max"),
    # each batch's answers handed to the wrong requests
    ("rows moved", lambda out: jnp.roll(out, 1, axis=0), "ref_rel_max"),
    # answers kept in bfloat16 and widened
    ("bfloat16", lambda out: out.astype(jnp.bfloat16).astype(jnp.float32),
     "bf16_exact_share"),
])
def test_an_altered_answer_is_not_correct(rehearse, monkeypatch, fault,
                                          alter, check):
    """The timed path broken underneath, where the engine produces the
    answers."""
    from repro.core import executor

    call = executor.CompiledNetwork.__call__
    monkeypatch.setattr(executor.CompiledNetwork, "__call__",
                        lambda self, *a, **k: alter(call(self, *a, **k)))
    rc, line = rehearse("mbv2.saturate")
    assert rc == 0
    assert line["correct"] is False
    checks = line["checks"]
    assert checks[check]["value"] > checks[check]["limit"]


def test_no_tpu_exits_nonzero_without_a_result():
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mbv2.stream",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "metrics" not in out.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no program to measure."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mbv2.stream",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "metrics" not in out.stdout


def test_control_fails_the_limit():
    """At a size a test can hold: each control, put in the program's place
    and through the comparison a run makes, reads not correct on three
    seeds: bfloat16 storage by ``bf16_exact_share``, 4-bit fixed point by
    ``ref_rel_max``."""
    from chipbench import control, run
    from chipbench.reference import CONTROLS
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    fails = {"control_bf16": "bf16_exact_share",
             "control_int4": "ref_rel_max"}
    assert set(fails) == set(CONTROLS)
    for cell in ("mbv2.stream", "shfl.saturate"):
        _, cfg, traffic = run.load_cell(bench, cell)
        cfg["serve"]["res"] = [32, 32]
        traffic = {**traffic, "images": 8}
        for seed in (1, 2**31 + 5, 7):
            r = control.readings(cfg, traffic, seed, program=False)
            assert set(r) == set(CONTROLS)
            for mode, check in fails.items():
                assert r[mode]["correct"] is False, (cell, seed, mode, r)
                assert np.isfinite(r[mode]["ref_rel_max"])
                limit = {"ref_rel_max": run.REF_REL_LIMIT,
                         "bf16_exact_share": run.BF16_SHARE_LIMIT}[check]
                assert r[mode][check] > limit, (cell, seed, mode, r)
