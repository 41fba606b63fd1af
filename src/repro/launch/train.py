"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Trains reduced configs by default and ``--full-config`` at published
widths; ``--data-parallel N`` builds a (data, model) mesh over the first
N devices.  ``build_trainer`` is the one Trainer construction, shared
with ``chip_smoke.py``.
"""
from __future__ import annotations

import argparse
from typing import Optional, Sequence

from repro import optim
from repro import parallel as PX
from repro.compile_cache import enable_compile_cache
from repro.data import DataConfig
from repro.models.registry import ARCH_IDS, build_model, get_config, \
    reduced_config
from repro.sharding import make_rules
from repro.train import Trainer, TrainerConfig


def _parse_reconfig_schedule(spec: str):
    """'10:4x1,20:1x4' -> [ReconfigEvent(step=10, mesh_shape=(4, 1)), …]"""
    from repro.elastic_driver import ReconfigEvent
    events = []
    for item in spec.split(","):
        try:
            step_s, shape_s = item.strip().split(":")
            pod_s, data_s = shape_s.lower().split("x")
            events.append(ReconfigEvent(step=int(step_s),
                                        mesh_shape=(int(pod_s),
                                                    int(data_s))))
        except ValueError as e:
            raise SystemExit(
                f"bad --reconfig-at entry {item!r} (want STEP:PODxDATA,"
                f" e.g. '10:4x1'): {e}")
    return events


def _model(args):
    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = reduced_config(cfg)
    return cfg, build_model(cfg, remat=args.full_config)


def build_elastic_driver(args: argparse.Namespace):
    """The --reconfig-at path's ``(ElasticDriver, schedule)``."""
    from repro.elastic_driver import ElasticDriver

    if not args.data_parallel:
        raise SystemExit("--reconfig-at needs --data-parallel (the "
                         "data axis of the initial factorization)")
    if args.model_parallel != 1:
        raise SystemExit("the elastic driver trains hier_bucketed_zero1 "
                         "on a pure (pod, data) mesh; --model-parallel "
                         "must be 1")
    # the driver pins its training configuration; reject sync flags it
    # would otherwise silently ignore ('xla' is the untouched default)
    if args.cross_pod_mode not in ("xla", "hier_bucketed_zero1"):
        raise SystemExit(
            f"--reconfig-at implies cross_pod_mode=hier_bucketed_zero1; "
            f"{args.cross_pod_mode!r} is not supported by the elastic "
            f"driver")
    if args.overlap:
        raise SystemExit("--overlap has no pipeline under the driver's "
                         "deterministic reduce")
    if args.slow_compress_bits and not (args.slow_compress_bits == 8
                                        and args.error_feedback):
        raise SystemExit(
            "the elastic driver compresses the slow hop only as int8 "
            "with error feedback (--slow-compress-bits 8 "
            "--error-feedback)")
    schedule = _parse_reconfig_schedule(args.reconfig_at)
    n_devices = args.pod_parallel * args.data_parallel
    for e in schedule:
        if e.mesh_shape[0] * e.mesh_shape[1] != n_devices:
            raise SystemExit(
                f"reconfig target {e.mesh_shape} is not a factorization "
                f"of {n_devices} devices")
        if e.step >= args.steps:
            raise SystemExit(
                f"reconfig step {e.step} is past the run "
                f"(--steps {args.steps}); it would silently never fire")
    from repro.faults.retry import RetryPolicy
    cfg, model = _model(args)
    drv = ElasticDriver(
        model,
        optim.AdamWConfig(peak_lr=args.lr, warmup_steps=20,
                          total_steps=args.steps),
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                   global_batch=args.batch),
        base_dir=args.ckpt_dir, bucket_bytes=args.bucket_mb << 20,
        accum=args.accum, mode=args.reconfig_mode,
        error_feedback=args.error_feedback,
        retry=RetryPolicy(max_retries=args.max_restore_retries),
        fallback_on_corrupt=args.fallback_on_corrupt)
    return drv, schedule


def _run_elastic(args) -> None:
    """--reconfig-at path: the elastic preemption/repack driver."""
    drv, schedule = build_elastic_driver(args)
    out = drv.run(args.steps, schedule,
                  initial_shape=(args.pod_parallel, args.data_parallel),
                  resume=args.resume)
    if out.start_step:
        print(f"resumed from committed step {out.start_step}")
    if out.recovery is not None and out.recovery.quarantined:
        for q in out.recovery.quarantined:
            print(f"quarantined corrupt step {q.step} -> "
                  f"{q.quarantined_to}")
    for i, (loss, shape) in enumerate(zip(out.losses, out.mesh_shapes),
                                      start=out.start_step):
        print(f"step {i:4d}  loss {loss:.4f}  mesh {shape}")
    for m in out.measurements:
        print(f"reconfig@{m.step}: {m.from_shape}->{m.to_shape} "
              f"[{m.mode}] save {m.save_s*1e3:.0f} ms, restore "
              f"{m.restore_s*1e3:.0f} ms, recompile "
              f"{m.compile_s*1e3:.0f} ms, verified={m.verified}")


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="commit a checkpoint after every N-th step")
    ap.add_argument("--log-every", type=int, default=10,
                    help="record loss and step time every N-th step")
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--data-parallel", type=int, default=0,
                    help="devices for a (dp, mp) mesh; 0 = single device")
    ap.add_argument("--model-parallel", type=int, default=1)
    from repro.train import CROSS_POD_MODES
    ap.add_argument("--cross-pod-mode", default="xla",
                    choices=CROSS_POD_MODES,
                    help="gradient sync schedule (bucketed modes need a "
                         "pure data-parallel mesh)")
    ap.add_argument("--bucket-mb", type=int, default=32,
                    help="bucket capacity for the hier_bucketed* modes")
    ap.add_argument("--overlap", action="store_true",
                    help="pipeline bucket i+1's fast reduce-scatter under "
                         "bucket i's slow hop (hier_bucketed* modes; "
                         "bitwise-identical losses)")
    ap.add_argument("--slow-compress-bits", type=int, default=0,
                    choices=(0, 8, 16),
                    help="compress the slow (cross-pod) hop: 16=bf16, "
                         "8=int8+scale")
    ap.add_argument("--error-feedback", action="store_true",
                    help="carry int8 quantization residuals across steps "
                         "(requires --slow-compress-bits 8 and a "
                         "hier_bucketed* mode)")
    ap.add_argument("--deterministic-reduce", action="store_true",
                    help="mesh-factorization-invariant gradient reduce "
                         "(hier_bucketed* modes): bitwise-identical "
                         "training across (pod, data) factorizations, so "
                         "sharded checkpoints reshard-restore exactly "
                         "onto a repacked mesh")
    ap.add_argument("--resume", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="resume from the latest committed checkpoint in "
                         "--ckpt-dir (--no-resume starts from scratch)")
    ap.add_argument("--max-restore-retries", type=int, default=0,
                    help="bounded exponential-backoff retries for "
                         "transient I/O (EIO/ENOSPC/...) during "
                         "checkpoint save and restore")
    ap.add_argument("--fallback-on-corrupt", action="store_true",
                    help="if the newest committed checkpoint fails its "
                         "CRC/manifest validation at resume, quarantine "
                         "it on disk and fall back to the previous "
                         "committed step instead of dying")
    ap.add_argument("--save-sharded", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="write per-rank shard + manifest checkpoints "
                         "(repro.ckpt); --no-save-sharded keeps the "
                         "legacy gathered per-leaf format")
    ap.add_argument("--reconfig-at", default="",
                    help="elastic repack schedule 'STEP:PODxDATA[,...]' "
                         "(e.g. '10:4x1,20:1x4'): run the elastic "
                         "driver, executing a save -> reshard-restore "
                         "-> continue cycle at each step; implies "
                         "hier_bucketed_zero1 + deterministic reduce")
    ap.add_argument("--reconfig-mode", default="handoff",
                    choices=("drain", "handoff"),
                    help="how --reconfig-at events move state: "
                         "'handoff' = committed sharded save + "
                         "reshard-restore (drain-free); 'drain' = "
                         "legacy gathered save + full restore (the "
                         "incumbent cycle, for cost comparison)")
    ap.add_argument("--pod-parallel", type=int, default=1,
                    help="pod axis of the initial (pod, data) "
                         "factorization for --reconfig-at runs")
    args = ap.parse_args(argv)

    # the recovery knobs act at restore time; with --no-resume there is
    # no restore, so accepting them would silently do nothing
    if not args.resume and args.fallback_on_corrupt:
        raise SystemExit("--fallback-on-corrupt is a resume-time "
                         "recovery knob; it does nothing with "
                         "--no-resume — drop one of the two")
    if not args.resume and args.max_restore_retries and not args.reconfig_at:
        raise SystemExit("--max-restore-retries needs a restore to "
                         "retry; with --no-resume (and no --reconfig-at "
                         "handoffs) it does nothing — drop one of the "
                         "two")
    if args.max_restore_retries < 0:
        raise SystemExit("--max-restore-retries must be >= 0")
    return args


def build_trainer(args: argparse.Namespace) -> Trainer:
    """The Trainer ``main`` runs for ``args`` (without --reconfig-at)."""
    cfg, model = _model(args)
    rules = None
    if args.data_parallel:
        mesh = PX.make_device_mesh(
            (args.data_parallel, args.model_parallel), ("data", "model"))
        # manual sync modes keep params replicated (train._check_manual_
        # sync_rules rejects FSDP rules), so build ZeRO-1-style rules
        from repro.train import MANUAL_SYNC_MODES
        rules = make_rules(
            mesh, fsdp=args.cross_pod_mode not in MANUAL_SYNC_MODES)

    return Trainer(
        model,
        optim.AdamWConfig(peak_lr=args.lr, warmup_steps=20,
                          total_steps=args.steps),
        TrainerConfig(n_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, log_every=args.log_every,
                      accum=args.accum,
                      cross_pod_mode=args.cross_pod_mode,
                      bucket_bytes=args.bucket_mb << 20,
                      slow_compress_bits=args.slow_compress_bits,
                      overlap=args.overlap,
                      slow_error_feedback=args.error_feedback,
                      deterministic_reduce=args.deterministic_reduce,
                      save_sharded=args.save_sharded),
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                   global_batch=args.batch),
        rules=rules)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    if args.reconfig_at:
        _run_elastic(args)
        return
    out = build_trainer(args).run(resume=args.resume)
    for h in out["history"]:
        print(f"step {h['step']:4d}  loss {h['loss']:.4f}  "
              f"{h['sec_per_step']*1e3:.0f} ms")


if __name__ == "__main__":
    main()
