"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --steps 200 --batch 8 --seq 128 --optimizer ef_signsgd \
        --strategy dense --reduced

On the CPU use ``--reduced`` (the smoke variant); on TPU chips drop it and
point ``--mesh-data/--mesh-model`` at the slice. The
``--strategy`` flag selects the gradient exchange (dense | ef_allgather |
ef_ring | ef_alltoall | majority_vote | ef_coord_median | ef_trimmed_mean |
ef_norm_filter); ``--overlap`` pipelines the compressed exchange with
backward compute (see README "Async overlap"); ``--byz-attack`` /
``--byz-fraction`` corrupt EF-worker lanes and ``--byz-f`` sets the robust
strategies' declared tolerance (see README "Byzantine robustness").
"""

from __future__ import annotations

import argparse
import json

from repro.comm.api import CommSpec
from repro.comm.backends import BACKEND_CHOICES
from repro.comm.bucketize import DEFAULT_BUCKET_SIZE
from repro.configs import get_config, reduced as make_reduced
from repro.configs.base import BYZ_ATTACKS, ByzConfig, OverlapConfig
from repro.fed.spec import FedSpec
from repro.launch.mesh import make_host_mesh
from repro.obs import sink as obs_sink
from repro.obs.telemetry import TELEMETRY_CHOICES
from repro.train.loop import TrainJob, run_training
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--optimizer", default="ef_signsgd")
    ap.add_argument("--strategy", default="dense")
    ap.add_argument("--compressor", default="scaled_sign")
    ap.add_argument("--policy", default=None)
    ap.add_argument("--momentum", type=float, default=0.0)
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument(
        "--bucket-size", type=int, default=None,
        help="comm-bucket elements (default: repro.comm's 65536; 0 = per-leaf path)",
    )
    ap.add_argument(
        "--backend", default="auto", choices=list(BACKEND_CHOICES),
        help="collective backend for the payload-mean exchange (repro.comm."
        "backends: xla | ring | pallas_dma; auto resolves per mesh — "
        "pallas_dma runs as ring on the CPU, with a logged reason)",
    )
    ap.add_argument(
        "--overlap", action="store_true",
        help="pipeline bucket compression + collectives with backward compute "
        "(repro.overlap; bucketed ef_allgather / ef_ring / majority_vote only "
        "— ef_alltoall's server shards aren't availability-sliceable)",
    )
    ap.add_argument(
        "--overlap-groups", type=int, default=None,
        help="overlap pipeline depth (bucket groups per step; implies --overlap)",
    )
    ap.add_argument(
        "--byz-attack", default=None, choices=list(BYZ_ATTACKS),
        help="fault injection: corrupt EF-worker lanes with this attack "
        "(repro.comm.adversary; any --byz-* flag enables the byz path)",
    )
    ap.add_argument(
        "--byz-fraction", type=float, default=None,
        help="fraction of EF workers the injector corrupts (floor(frac*W) lanes)",
    )
    ap.add_argument(
        "--byz-f", type=int, default=None,
        help="declared adversary tolerance for the robust strategies "
        "(ef_coord_median / ef_trimmed_mean / ef_norm_filter; needs 2f < W)",
    )
    ap.add_argument(
        "--byz-scale", type=float, default=None,
        help="attack magnitude for scaled_noise / const_drift (default 10.0)",
    )
    ap.add_argument(
        "--clients", type=int, default=None,
        help="federated tier (repro.fed): simulate this many clients; any "
        "--clients/--cohort/--participation/--shard-skew flag enables fed "
        "rounds (needs --strategy ef_allgather; steps count rounds, --batch "
        "is per-client)",
    )
    ap.add_argument(
        "--cohort", type=int, default=None,
        help="clients sampled per federated round (absolute; exclusive with "
        "--participation; a cohort of 0 is rejected at spec validation)",
    )
    ap.add_argument(
        "--participation", type=float, default=None,
        help="fraction of clients sampled per round, in (0, 1]; a fraction "
        "that rounds to 0 clients is rejected at spec validation",
    )
    ap.add_argument(
        "--shard-skew", type=float, default=None,
        help="non-IID label skew in [0, 1]: narrows each client's vocab "
        "window (0 = IID, 1 = disjoint minimal windows)",
    )
    ap.add_argument(
        "--size-skew", type=float, default=None,
        help="power-law exponent of per-client dataset sizes (feeds the "
        "FedAvg weights; 0 = uniform sizes)",
    )
    ap.add_argument(
        "--fed-staleness", type=int, default=None,
        help="async-round mode: mix the applied update from the last D+1 "
        "round aggregates with 1/(1+age) staleness weights (0 = synchronous)",
    )
    ap.add_argument(
        "--telemetry", default="off", choices=list(TELEMETRY_CHOICES),
        help="in-graph telemetry level (repro.obs): 'full' records per-group "
        "EF-residual norms, densities and exact wire bytes each logged step; "
        "'off' compiles to the exact untelemetered program",
    )
    ap.add_argument(
        "--log-dir", default="",
        help="write a schema-versioned run.jsonl of run records here "
        "(summarize with `python -m repro.obs report <file>`)",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    mesh = make_host_mesh(data=args.mesh_data, model=args.mesh_model)
    bucket_size = DEFAULT_BUCKET_SIZE
    if args.bucket_size is not None:
        bucket_size = args.bucket_size or None  # 0 → per-leaf fallback
    spec = CommSpec(
        strategy=args.strategy,
        compressor=args.compressor,
        bucket_size=bucket_size,
        backend=args.backend,
        overlap=OverlapConfig.from_args(args.overlap, args.overlap_groups),
        byz=ByzConfig.from_args(args.byz_attack, args.byz_fraction, args.byz_f, args.byz_scale),
        telemetry=args.telemetry,
        fed=FedSpec.from_args(
            args.clients, args.cohort, args.participation,
            args.shard_skew, args.size_skew, args.fed_staleness,
        ),
    ).validate()  # reject bad flag combinations before any compile
    job = TrainJob(
        cfg=cfg, mesh=mesh, steps=args.steps, batch=args.batch, seq=args.seq,
        lr=args.lr, momentum=args.momentum, weight_decay=args.weight_decay,
        optimizer=args.optimizer, compressor=args.compressor,
        policy=args.policy, seed=args.seed,
        microbatches=args.microbatches, comm=spec,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        log_dir=args.log_dir,
    )
    _, history = run_training(job, log_fn=lambda r: print(json.dumps(r), flush=True))
    # epilogue from the unconditional final record — history[-1] raised
    # IndexError on zero-step runs
    final = obs_sink.final_record(history, steps=args.steps)
    print(json.dumps(final), flush=True)
    fl = final["final_loss"]
    print(f"final_loss={fl:.4f}" if fl is not None else "final_loss=nan")


if __name__ == "__main__":
    main()
