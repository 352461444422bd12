"""Readings that set a cell's limits: sound runs, the control, the faults.

    python3 chipbench/calibrate.py --workload <cell> --first-seed <n> --seeds 12 --control-seeds 3

In one process, so the step compiles once: for each of ``--seeds`` seeds,
the program's first three steps against the float32 reference (the lower
readings). For the first ``--control-seeds`` of them, also the reference put
in the program's place in the control precision (``fp8``) and with each fault
the cell can have planted (``half_batch``, ``bit_order`` where the cell runs
the EF exchange, ``no_exchange`` where there are several workers), each
against the float32 reference (the upper readings), and the reference in
the stated precision (``bf16``), a witness of what rounding alone reads.
A state left unchanged reads 1 on ``grad`` and ``update`` by their
definition and needs no run. The sign shares are also read at the floors
``FLOORS``, for the worst leaf and pooled over the leaves.

``--program-f32`` runs the program with float32 products at the highest
matmul precision instead of the configuration's bfloat16: a second witness,
which has to agree with the reference far more closely than the timed
configuration does where the program's semantics are the reference's. It
runs where the bucket kernels accept that global precision: on the CPU, at
a small size (on a TPU, Mosaic refuses the bit-pack kernel's bfloat16
product at float32 precision).

One JSON line per reading on stdout, then a summary line: for each number,
the largest sound reading and the least reading of each variant. Needs the
chip, as ``run.py`` does; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

import jax
import numpy as np

import run
from run import cells, check


FLOORS = (0.3, 1.0, 2.0)


def _paths(tree) -> list:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


def _worst_leaves(got, ref, top: int = 3) -> list:
    """The leaves whose first gradient differs most (relative), by path."""
    diffs, norms = check.leaf_diffs(got.first_grad, ref.first_grad)
    rel = diffs / np.maximum(norms, np.median(norms))
    paths = _paths(ref.first_grad)
    return [[paths[i], float(rel[i])] for i in np.argsort(-rel)[:top]]


def _signs(got, ref) -> dict:
    """Sign shares at each floor: the worst leaf (with its path) and pooled."""
    keep = np.asarray(ref.grad_norms[0]) >= check.TINY * np.median(ref.grad_norms[0])
    paths = _paths(ref.first_grad)
    out = {}
    for name, a, b in (("grad", got.first_grad, ref.first_grad), ("update", got.change, ref.change)):
        counted, bad = check.sign_counts(a, b, FLOORS)
        share = np.where(keep[:, None], bad / np.maximum(counted, 1), -1.0)
        for j, floor in enumerate(FLOORS):
            i = int(np.argmax(share[:, j]))
            pooled = bad[keep, j].sum() / max(counted[keep, j].sum(), 1)
            out[f"{name}_sign@{floor}"] = [float(share[i, j]), float(pooled), paths[i]]
    return out


def _to_host(r):
    """Move a reading's trees off the device, so the next run has its memory."""
    r.first_grad, r.change = jax.device_get(r.first_grad), jax.device_get(r.change)
    return r


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--program-f32", action="store_true")
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    if args.program_f32:
        jax.config.update("jax_default_matmul_precision", "highest")
        cell = dataclasses.replace(cell, config={**cell.config, "compute_dtype": "float32"})
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs a TPU with the cell's chips", file=sys.stderr)
        return 2
    from repro.launch.mesh import use_mesh

    run.enable_cache()
    variants = {"fp8": {"precision": "fp8"}, "half_batch": {"fault": "half_batch"}}
    if cell.ef:
        variants["bit_order"] = {"fault": "bit_order"}
    if cell.workers > 1:
        variants["no_exchange"] = {"fault": "no_exchange"}
    variants["bf16"] = {"precision": "bf16"}
    if args.program_f32:
        variants = {}
    sound, upper = [], {k: [] for k in variants}
    for n in range(args.seeds):
        seed = args.first_seed + n
        t = time.perf_counter()
        mesh, pool, prep = run.build(cell, seed)
        chips = list(mesh.devices.flat)
        with use_mesh(mesh):
            state, program = run.first_steps(cell, prep, pool)
            del state, prep
            gc.collect()
        ref = _to_host(run.reference_readings(cell, seed, pool, chips))
        got = check.compare(program, ref)
        sound.append(got)
        print(json.dumps({"seed": seed, "reading": "program", **got,
                          "losses": program.losses, "ref_losses": ref.losses,
                          "worst_grad_leaves": _worst_leaves(program, ref),
                          "signs": _signs(program, ref),
                          "s": time.perf_counter() - t}), flush=True)
        del program
        if n < args.control_seeds:
            for name, kw in variants.items():
                alt = run.reference_readings(cell, seed, pool, chips, **kw)
                got = check.compare(alt, ref)
                upper[name].append(got)
                print(json.dumps({"seed": seed, "reading": name, **got,
                                  "worst_grad_leaves": _worst_leaves(alt, ref),
                                  "signs": _signs(alt, ref)}), flush=True)
                del alt
                gc.collect()
        del pool, ref
        gc.collect()
    keys = tuple(sound[0])
    summary = {
        "lower": {k: max(r[k] for r in sound) for k in keys},
        "upper": {name: {k: min(r[k] for r in rs) for k in keys} for name, rs in upper.items()},
        "seeds": args.seeds,
    }
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
