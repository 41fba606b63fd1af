"""Bring-up check: the training path and the elastic handoff on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the elastic handoff only

One chip trains xlstm-125m at its published config (12 blocks, d_model
768, vocab 50304; random weights from seed 0) at seq 2048 and global
batch 8, with the Trainer that ``python -m repro.launch.train`` builds
for the same flags.  It checks that
  * every loss is finite and the last is below the first;
  * the run commits a sharded checkpoint that a second Trainer restores
    and continues from, reproducing the first run's losses exactly;
  * the loss of the trained weights on one sequence agrees with the same
    forward in float32 on the host's CPU backend.

Four chips run the elastic driver (``hier_bucketed_zero1`` with the
deterministic reduce) on a (pod, data) = (2, 2) mesh with one handoff to
(4, 1), against an uninterrupted (2, 2) run in the same process: losses
must match bitwise, the handoff must verify its restored state bitwise,
and every chip must hold its own ZeRO-1 shard and batch rows.

Fails (non-zero exit, no result line) when JAX finds no TPU.  The last
line of stdout is one JSON object naming the device.  The persistent
compile cache is ``$JAX_COMPILATION_CACHE_DIR`` or ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

ARCH = "xlstm-125m"
SEQ, BATCH = 2048, 8
TRAIN_STEPS, CKPT_STEP, LR = 10, 8, 1e-3
HANDOFF_STEPS, HANDOFF_AT = 4, 2
N_CHIPS = 4                  # the handoff runs (2, 2) -> (4, 1)
# The chip computes in bfloat16 (weights stored in bf16, f32 accumulation);
# the reference runs the same bf16-valued weights in float32 at "highest"
# matmul precision.  The comparison is made at the trained weights: at the
# initial ones every near-uniform output, a broken forward's included,
# scores close to ln V.  A forward with constant logits (the control,
# computed by the same loss code) must lie at least CONTROL_MARGIN
# tolerances from the reference, or the check could not tell it apart.
# At the seed-0 initial weights a TPU v5e read rel 4.4e-4 against the
# reference, and constant logits lie only rel 7.5e-3 from it.  At the
# step-10 weights it read rel 1.2e-6, and constant logits rel 0.40: the
# limit lies between the two, leaving room for bf16 rounding (2**-9 per
# value) to average out less well on other data.
REF_RTOL = 5e-3
CONTROL_MARGIN = 10


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def train_argv(ckpt_dir: str, *, steps: int, resume: bool,
               full_config: bool = True, seq: int = SEQ,
               batch: int = BATCH) -> list:
    """``repro.launch.train`` flags for the one-chip phase."""
    argv = ["--arch", ARCH, "--seq", str(seq), "--batch", str(batch),
            "--steps", str(steps), "--lr", str(LR),
            "--ckpt-dir", ckpt_dir, "--ckpt-every", str(CKPT_STEP),
            "--log-every", "1", "--resume" if resume else "--no-resume"]
    return argv + (["--full-config"] if full_config else [])


def reference_check(model, data_cfg, params) -> dict:
    """Loss of ``params`` on sequence 0 of batch 0: on the default device
    against float32 on the host's CPU backend, and constant logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import SyntheticCorpus
    from repro.models.layers import softmax_xent

    seq = {k: v[:1] for k, v in SyntheticCorpus(data_cfg).batch(0).items()}
    loss = jax.jit(lambda p, b: model.loss(p, b)[0])
    chip = float(loss(params, seq))
    cpu = jax.devices("cpu")[0]
    p32 = jax.device_put(jax.tree.map(
        lambda a: np.asarray(a, np.float32), jax.device_get(params)), cpu)
    with jax.default_matmul_precision("highest"):
        ref = float(loss(p32, jax.device_put(seq, cpu)))
    with jax.default_device(cpu):
        const = float(sum(softmax_xent(
            jnp.zeros((1, 1, data_cfg.vocab_size), jnp.float32),
            jnp.zeros((1, 1), jnp.int32))))
    rel = abs(chip - ref) / abs(ref)
    rel_const = abs(const - ref) / abs(ref)
    print(f"reference: seq-0 loss device {chip!r}  cpu-f32 {ref!r}  "
          f"|diff| {abs(chip - ref)!r}  rel {rel!r} (tol {REF_RTOL}); "
          f"constant logits {const!r}, rel {rel_const!r}")
    check(rel_const >= CONTROL_MARGIN * REF_RTOL,
          f"constant logits score rel {rel_const} from the reference: the "
          f"weights carry too little to test the forward at tol {REF_RTOL}")
    check(math.isfinite(chip) and rel <= REF_RTOL,
          f"device loss {chip} differs from the f32 reference {ref} by "
          f"rel {rel} > {REF_RTOL}")
    return {"device": chip, "cpu_f32": ref, "rel": rel,
            "constant": const, "rel_constant": rel_const}


def train_phase(**size) -> None:
    from repro.launch import train as launch_train

    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        args = launch_train.parse_args(
            train_argv(ckpt_dir, steps=TRAIN_STEPS, resume=False, **size))
        trainer = launch_train.build_trainer(args)
        out = trainer.run(resume=args.resume)
        hist = out.pop("history")
        del out                                 # frees the device state
        losses = [h["loss"] for h in hist]
        times = [h["sec_per_step"] for h in hist]
        steady = statistics.median(times[1:CKPT_STEP])
        print(f"train: losses {losses}")
        print(f"train: first step {times[0]!r} s (compile "
              f"{times[0] - steady!r} s), steady step {steady!r} s "
              f"(median of steps 1..{CKPT_STEP - 1}, blocked on outputs), "
              f"peak bytes in use {peak_bytes_in_use(1)}")
        check([h["step"] for h in hist] == list(range(TRAIN_STEPS)),
              f"history steps {[h['step'] for h in hist]}")
        check(all(math.isfinite(x) for x in losses),
              f"non-finite loss in {losses}")
        check(losses[-1] < losses[0],
              f"last loss {losses[-1]} is not below the first {losses[0]}")

        from repro import ckpt
        check(ckpt.committed_steps(ckpt_dir) == [CKPT_STEP],
              f"committed steps {ckpt.committed_steps(ckpt_dir)}, "
              f"want [{CKPT_STEP}]")
        args2 = launch_train.parse_args(
            train_argv(ckpt_dir, steps=TRAIN_STEPS, resume=True, **size))
        trainer2 = launch_train.build_trainer(args2)
        out2 = trainer2.run(resume=True)
        hist2, params = out2.pop("history"), out2.pop("params")
        del out2                                # frees the optimizer state
        resumed = [h["loss"] for h in hist2]
        print(f"resume: from committed step {CKPT_STEP}, losses {resumed}, "
              f"first step {hist2[0]['sec_per_step']!r} s (a new jit: "
              f"compiles again or reads the persistent cache)")
        check([h["step"] for h in hist2]
              == list(range(CKPT_STEP, TRAIN_STEPS)),
              f"resumed steps {[h['step'] for h in hist2]}")
        check(resumed == losses[CKPT_STEP:],
              f"resumed losses {resumed} != uninterrupted "
              f"{losses[CKPT_STEP:]}")
        reference_check(trainer2.model, trainer2.data_cfg, params)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def peak_bytes_in_use(n: int) -> dict:
    """Peak device memory of the first ``n`` devices, where reported."""
    import jax
    return {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()[:n]}


def _bytes_per_device(tree) -> dict:
    import jax
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return dict(sorted(out.items()))


def placement_check(ref, data_cfg) -> None:
    """Each chip holds its own ZeRO-1 shard (2, 2) and its own batch rows."""
    import jax
    from repro import parallel as PX
    from repro.data import SyntheticCorpus
    from repro.sharding import make_rules
    from repro.train import put_batch

    all_ids = sorted(d.id for d in jax.devices()[:N_CHIPS])
    master = ref.opt_state.master
    mesh = master[0].sharding.mesh
    print(f"handoff: (2,2) mesh device ids {mesh.device_ids.tolist()}")
    fast = mesh.shape["data"]
    for b in master:
        ids = sorted(sh.device.id for sh in b.addressable_shards)
        check(ids == all_ids, f"master bucket on devices {ids}")
        check(all(sh.data.shape[0] * fast == b.shape[0]
                  for sh in b.addressable_shards),
              f"master bucket {b.shape} not split {fast} ways over 'data'")
    print(f"handoff: (2,2) ZeRO-1 bytes per device "
          f"{_bytes_per_device(ref.opt_state)}, params "
          f"{_bytes_per_device(ref.params)}")
    rules = make_rules(PX.make_device_mesh((2, 2), ("pod", "data")),
                       fsdp=False)
    batch = put_batch(SyntheticCorpus(data_cfg).batch(0), rules)
    rows = {sh.device.id: sh.index[0] for sh in
            batch["tokens"].addressable_shards}
    print(f"handoff: batch rows per device {rows}")
    check(sorted(rows) == all_ids
          and all(r.stop - r.start == data_cfg.global_batch // N_CHIPS
                  for r in rows.values()),
          f"batch rows per device {rows}")


def handoff_phase(**size) -> None:
    from repro.launch import train as launch_train

    dirs = [tempfile.mkdtemp(prefix="chip_smoke_elastic_")
            for _ in range(2)]
    try:
        def driver(base_dir):
            argv = train_argv(base_dir, steps=HANDOFF_STEPS, resume=False,
                              **size)
            argv += ["--pod-parallel", "2", "--data-parallel", "2",
                     "--reconfig-at", f"{HANDOFF_AT}:{N_CHIPS}x1"]
            return launch_train.build_elastic_driver(
                launch_train.parse_args(argv))

        ref_drv, _ = driver(dirs[0])
        t0 = time.perf_counter()
        ref = ref_drv.run(HANDOFF_STEPS, [], initial_shape=(2, 2))
        print(f"handoff: uninterrupted (2,2) losses "
              f"{ref.losses} ({time.perf_counter() - t0!r} s, first step "
              f"{ref.first_step_s!r} s, steady {ref.steady_step_s!r} s)")
        placement_check(ref, ref_drv.data_cfg)
        ref_losses = ref.losses
        del ref

        drv, schedule = driver(dirs[1])
        out = drv.run(HANDOFF_STEPS, schedule, initial_shape=(2, 2))
        print(f"handoff: with handoff losses {out.losses} meshes "
              f"{out.mesh_shapes}")
        for m in out.measurements:
            print(f"handoff: {m.from_shape}->{m.to_shape} at step {m.step}:"
                  f" save {m.save_s!r} s, restore {m.restore_s!r} s, "
                  f"setup {m.setup_s!r} s, first step {m.first_step_s!r} s"
                  f" (compile {m.compile_s!r} s), verified={m.verified}")
        final = out.params["embed"].sharding.mesh
        print(f"handoff: ({N_CHIPS},1) mesh device ids "
              f"{final.device_ids.tolist()}, state bytes per device "
              f"{_bytes_per_device((out.params, out.opt_state))}")
        check(len(out.measurements) == 1 and out.measurements[0].verified,
              "the handoff did not verify its restored state")
        check(out.losses == ref_losses,
              f"handoff losses {out.losses} != uninterrupted {ref_losses}")
        print("handoff: losses bitwise equal to the uninterrupted run; peak "
              f"bytes in use {peak_bytes_in_use(N_CHIPS)}")
    finally:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, N_CHIPS),
                    help="1: train + checkpoint/resume on one chip; "
                         "4: the elastic handoff across four chips")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    # the reference forward needs the host's CPU backend beside the TPU
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    print(f"device: {devs[0].device_kind} x{len(devs)}, jax "
          f"{jax.__version__}, compile cache {enable_compile_cache()}")
    try:
        if args.chips == N_CHIPS:
            handoff_phase()
        else:
            train_phase()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
