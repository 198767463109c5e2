#!/usr/bin/env python3
"""Readings of the correctness comparison at a cell's own size: the
program's, and its controls'.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3

For each seed: the cell's network built from the seed through the
program's worker path (``build_server``, one replica), and the images a
run with that seed sends, served through the server's ``submit`` in
batches of the largest bucket.  Their answers go through
``run.check_answers`` and ``run.passed``, as a run's do.  Then each control
(``reference.CONTROLS``: the reference one precision step below what the
configuration states) goes through the same comparison in the program's
place.  Prints one JSON line per seed, and exits 1 where the program read
not correct, or a control correct, on any seed.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import base64
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import loadgen, run  # noqa: E402


def as_result(rows) -> dict:
    """Answers ``rows`` (one per image) in the form the load generator
    reports them: every image answered once."""
    return {"rows": {str(i): base64.b64encode(
                np.ascontiguousarray(r, np.float32).tobytes()).decode()
                     for i, r in enumerate(rows)},
            "mismatch": {}, "records": []}


def program_rows(cfg: dict, seed: int, images):
    """The program's answers to ``images``, served by a server built from
    ``seed``."""
    from chipbench.reference import weight_seed
    from repro.frontend.worker import build_server

    serve = {**cfg["serve"], "seed": weight_seed(seed), "replicas": 1}
    server = build_server({"networks": [serve]})
    try:
        name = serve.get("as") or serve["name"]
        bucket = max(server.stats()["engines"][name]["buckets"])
        out = []
        for i in range(0, len(images), bucket):
            out += [f.result(timeout=120) for f in
                    server.submit_many(name, images[i:i + bucket])]
    finally:
        server.shutdown()
    return [np.asarray(r).reshape(-1) for r in out]


def readings(cfg: dict, traffic: dict, seed: int,
             program: bool = True) -> dict:
    """{"program" and each control: the comparison's numbers and whether
    they pass} for the images a run with ``seed`` sends."""
    from chipbench.reference import CONTROLS, Reference
    shape = [*cfg["serve"]["res"], 3]
    images = loadgen.make_images(seed, int(traffic["images"]), shape)
    answers = {}
    if program:
        answers["program"] = program_rows(cfg, seed, images)
    ref = Reference(cfg, seed)
    answers.update((mode, ref(images, mode)) for mode in CONTROLS)
    out = {}
    for who, rows in answers.items():
        checks = run.check_answers(cfg, seed, traffic, shape,
                                   as_result(rows))
        out[who] = {"correct": run.passed(checks),
                    **{k: c["value"] for k, c in checks.items()}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, cfg, traffic = run.load_cell(bench, args.workload)
    run.require_devices(1)
    run.enable_compile_cache()
    bad = False
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cfg, traffic, seed)
        bad |= (not r["program"]["correct"]) or any(
            v["correct"] for k, v in r.items() if k != "program")
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "limit": run.REF_REL_LIMIT, **r}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
