"""Training step factory + fault-tolerant training loop.

``make_train_step`` builds the jit-able (params, opt, batch) -> (params,
opt, metrics) function with:
- microbatch gradient accumulation (lax.scan) — required to fit the 100B
  archs' activations in 16 GB/chip;
- per-layer remat (inside the models' scanned stacks);
- cross-pod gradient sync modes (``cross_pod_mode``):

  * ``'xla'``         SPMD inserts the minimal sharded all-reduce.
  * ``'compressed'``  retired: its partial shard_map (manual 'pod',
                      auto 'data') fatally aborts XLA under the pinned
                      jax — multi-pod meshes get a NotImplementedError
                      pointing at ``hier_bucketed`` +
                      ``slow_compress_bits=8`` (same int8 slow hop).
  * ``'hier'``        fully-manual per-tensor hierarchical schedule
                      (reduce-scatter fast / psum slow / all-gather
                      fast) — 3 collectives *per leaf*; kept as the
                      latency-bound baseline the bucketed modes beat.
  * ``'hier_bucketed'``        the hierarchical schedule once per flat
                      f32 *bucket* (``collectives.bucketing``) — a
                      handful of large collectives per step.
  * ``'hier_bucketed_zero1'``  bucketed + shard-resident optimizer: the
                      schedule stops after the slow hop, AdamW updates
                      each rank's bucket shard (f32 masters sharded over
                      the fast axis) and updated *params* are
                      all-gathered instead of gradients.  Bitwise-
                      identical losses to ``hier_bucketed``.

``Trainer`` adds checkpoint/restart, straggler detection, failure
injection and host spans (``repro.tracing``) around the step function.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import checkpoint as legacy_ckpt
from repro import ckpt as ckpt_lib
from repro import optim
from repro import parallel as PX
from repro import tracing
from repro.collectives import bucketing
from repro.collectives import deterministic as det
from repro.collectives.hierarchical import hier_all_reduce_mean
from repro.data import DataConfig, Prefetcher, SyntheticCorpus
from repro.elastic import StragglerDetector
from repro.sharding import (MeshRules, batch_axes, fit_spec,
                            grad_sync_axes, use_rules, without_axes)

MANUAL_SYNC_MODES = ("hier", "hier_bucketed", "hier_bucketed_zero1")
BUCKETED_SYNC_MODES = ("hier_bucketed", "hier_bucketed_zero1")
CROSS_POD_MODES = ("xla", "compressed") + MANUAL_SYNC_MODES


class EFState(NamedTuple):
    """Optimizer state + int8 error-feedback residuals.

    ``residuals`` holds, per bucket, the part of each rank's (fast-axis
    reduce-scattered) gradient shard the int8 slow hop could not
    represent, carried across steps so the quantization noise telescopes
    (``collectives.compression.compressed_psum_mean_ef``).  Globally each
    residual is a flat ``(S * bucket_size,)`` f32 array sharded over
    (slow, fast) — every (pod, data) rank owns its private slice, since
    quantization error is per-rank state.
    """

    opt: Any                       # OptState | BucketedOptState
    residuals: Tuple[jax.Array, ...]


def _split_micro(batch: Dict[str, jax.Array], accum: int):
    def f(x):
        return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
    return {k: f(v) for k, v in batch.items()}


def _merge_micro(model, counts, accum: int):
    """One step's counters from its microbatches' (stacked on axis 0),
    merged by the model's own ``merge_counters``."""
    if not counts:
        return {}
    parts = [jax.tree.map(lambda c: c[i], counts) for i in range(accum)]
    return functools.reduce(model.merge_counters, parts)


def make_loss_and_grad(model, *, accum: int):
    """Pod-local accumulated (loss, grads, counters) over ``accum``
    microbatches; ``counters`` is the model's ``metrics["counters"]``
    (empty where it has none), merged over microbatches by its
    ``merge_counters``.

    Differentiates wrt an f32 view of the params (cast back to their
    storage dtype inside the loss, so the forward math is unchanged):
    grads then materialize and combine in f32 end-to-end.  Differentiating
    wrt the bf16 leaves directly rounds each microbatch's gradient — e.g.
    the tied embedding's lookup-scatter + logits-matmul contributions — to
    bf16 before accumulation, which breaks accum-invariance.

    Cost: the f32 view is a transient 2x-param-bytes buffer live during
    the accumulation scan (it dies before the optimizer update, which
    holds its own f32 masters).  The bucketed sync modes use
    ``collectives.bucketing.make_bucket_loss_and_grad`` instead, which
    differentiates wrt flat f32 buckets (same transient footprint, but
    no per-leaf f32 tree, flat gradient accumulation, and — in the
    zero1 mode — 1/F-sharded instead of replicated f32 masters).
    """

    def fn(params, batch):
        micro = _split_micro(batch, accum)
        dtypes = jax.tree.map(lambda p: p.dtype, params)
        params32 = jax.tree.map(
            lambda p: p.astype(jnp.float32), params)

        def cast_loss(p32, mb):
            p = jax.tree.map(lambda q, dt: q.astype(dt), p32, dtypes)
            return model.loss(p, mb)

        def step(carry, mb):
            loss_sum, grads = carry
            (loss, metrics), g = jax.value_and_grad(
                cast_loss, has_aux=True)(params32, mb)
            grads = jax.tree.map(lambda a, b: a + b, grads, g)
            return (loss_sum + loss, grads), metrics.get("counters", {})

        zero_g = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, grads), counts = jax.lax.scan(
            step, (jnp.zeros((), jnp.float32), zero_g), micro)
        inv = 1.0 / accum
        return (loss_sum * inv, jax.tree.map(lambda g: g * inv, grads),
                _merge_micro(model, counts, accum))

    return fn


def make_bucket_layout(params_or_shapes, mesh=None, *,
                       bucket_bytes: int = bucketing.DEFAULT_BUCKET_BYTES,
                       deterministic: bool = False
                       ) -> bucketing.BucketLayout:
    """The bucket layout the bucketed train modes derive for this mesh.

    Alignment is the fast-axis size so reduce-scatter divides every
    bucket evenly; passing the same (tree, mesh, bucket_bytes) the step
    sees — concrete params, ``jax.eval_shape`` output, either works —
    yields the exact layout, which is what ``optim.init_bucketed`` needs.

    ``deterministic=True`` (the ``deterministic_reduce`` train modes)
    aligns instead to ``lcm(fast, DETERMINISTIC_ALIGN)``, making the
    padded bucket sizes — and therefore every checkpointed flat array
    shape — identical across mesh factorizations whose fast size divides
    the constant.  That shape invariance is what lets a sharded
    checkpoint reshard *exactly* onto a re-factorized mesh.
    """
    fast_axis, _ = grad_sync_axes(mesh)
    fast = mesh.shape[fast_axis] if (mesh is not None and fast_axis) else 1
    align = det.det_align(fast) if deterministic else fast
    return bucketing.plan_buckets(params_or_shapes,
                                  bucket_bytes=bucket_bytes, align=align)


def _residual_spec(fast_axis, slow_axis) -> P:
    """PartitionSpec of one global error-feedback residual array."""
    axes = tuple(a for a in (slow_axis, fast_axis) if a)
    return P(axes) if axes else P()


def init_slow_residuals(params_or_shapes, mesh=None, *,
                        bucket_bytes: int = bucketing.DEFAULT_BUCKET_BYTES,
                        deterministic: bool = False
                        ) -> Tuple[jax.Array, ...]:
    """Zero error-feedback residuals for ``slow_error_feedback=True``.

    One flat f32 array per bucket of the layout the train step derives.
    Global size is ``S * bucket_size`` (S = slow-axis size): sharded over
    (slow, fast), each rank holds a residual the shape of its fast-axis
    reduce-scattered bucket shard.

    With ``deterministic=True`` each rank quantizes its *own full-bucket
    contribution* instead of a hierarchical shard, so the global size is
    ``R * bucket_size`` (R = total sync ranks) — invariant under mesh
    re-factorization, which is what lets the residuals reshard exactly
    on an elastic restore (the hierarchical variant's shard assignment
    follows the pod structure and cannot).
    """
    layout = make_bucket_layout(params_or_shapes, mesh,
                                bucket_bytes=bucket_bytes,
                                deterministic=deterministic)
    fast_axis, slow_axis = grad_sync_axes(mesh)
    ns = mesh.shape[slow_axis] if (mesh is not None and slow_axis) else 1
    nf = mesh.shape[fast_axis] if (mesh is not None and fast_axis) else 1
    n = ns * nf if deterministic else ns
    return tuple(jnp.zeros((n * c,), jnp.float32)
                 for c in layout.bucket_sizes)


def init_sharded_zero1(ocfg: optim.AdamWConfig, params, layout, mesh):
    """Build the ZeRO-1 opt state *already sharded* over the fast axis.

    Returns ``(BucketedOptState, shardings)`` where ``shardings`` is the
    matching tree of ``NamedSharding``s (None off-mesh).  Each rank
    materializes only its 1/F slice — a device_put after an unsharded
    init would transiently hold 3x full-model f32 on one device, the
    exact peak ZeRO-1 exists to avoid.  The single construction the
    trainer, the checkpoint bench and the reshard tests all share, so
    the state/sharding shapes cannot drift apart.
    """
    fast_axis, _ = grad_sync_axes(mesh)
    if mesh is None or not fast_axis:
        return optim.init_bucketed(ocfg, params, layout), None
    bshard = NamedSharding(mesh, P(fast_axis))
    shardings = optim.BucketedOptState(
        step=NamedSharding(mesh, P()),
        mu=(bshard,) * layout.n_buckets,
        nu=(bshard,) * layout.n_buckets,
        master=(bshard,) * layout.n_buckets)
    init_fn = jax.jit(lambda p: optim.init_bucketed(ocfg, p, layout),
                      out_shardings=shardings)
    return init_fn(params), shardings


# logical axes that shard *parameters* (vs batch/sequence activations) —
# the manual sync modes keep params replicated, so rules mapping any of
# these onto a real mesh axis would be silently ignored; reject instead
_PARAM_LOGICAL_AXES = ("embed", "heads", "kv_heads", "ff", "vocab",
                       "expert", "state", "conv", "norm", "lora")


def _check_manual_sync_rules(rules: Optional[MeshRules]) -> None:
    if rules is None or rules.mesh is None:
        return
    bad = {k: v for k, v in rules.rules.items()
           if k in _PARAM_LOGICAL_AXES and v is not None
           and PX.axes_size(rules.mesh, v) > 1}
    if bad:
        raise ValueError(
            f"manual gradient-sync modes keep params replicated, but the "
            f"rules shard parameter axes {bad} (FSDP/TP) — build rules "
            f"with make_rules(mesh, fsdp=False) or use "
            f"cross_pod_mode='xla'")


def _make_manual_sync_step(model, ocfg: optim.AdamWConfig, *, accum: int,
                           rules: Optional[MeshRules], mode: str,
                           bucket_bytes: int, slow_compress_bits: int,
                           overlap: bool = False,
                           slow_error_feedback: bool = False,
                           deterministic_reduce: bool = False):
    """The fully-manual (shard_map over pod+data) gradient-sync steps.

    With no mesh (or a 1-device one) every collective degenerates to the
    identity and the same code runs locally — that is what makes the
    single-process CPU equivalence tests possible.

    ``overlap`` pipelines consecutive buckets' syncs (bucketed modes;
    bitwise-identical results — see ``hier_reduce_bucket_shards``).
    ``slow_error_feedback`` carries int8 quantization residuals across
    steps; the step's opt-state argument then is an :class:`EFState`.
    ``deterministic_reduce`` swaps the hierarchical reduce for the
    mesh-factorization-invariant gather + fixed-tree fold
    (:mod:`repro.collectives.deterministic`): losses, grad norms and
    updates are then bitwise-identical across every (pod, data)
    factorization of the same rank count — the property the sharded
    checkpoint's reshard-on-restore acceptance test verifies.
    """
    _check_manual_sync_rules(rules)
    mesh = rules.mesh if rules is not None else None
    fast_axis, slow_axis = grad_sync_axes(mesh)
    sync_axes = tuple(a for a in (mesh.axis_names if mesh is not None
                                  else ()) if a in ("pod", "data"))
    n_sync = PX.axes_size(mesh, sync_axes)
    if n_sync == 1:
        # degenerate (single-cell) mesh: no shard_map is emitted, so the
        # axis names must not reach any collective either
        sync_axes = ()
        fast_axis = slow_axis = None
    ef = slow_error_feedback
    dt = deterministic_reduce
    lg = make_loss_and_grad(model, accum=accum)

    # inside the shard_map body the sync axes are mapped manually, so
    # model-code sharding constraints must not mention them.  Newer JAX
    # exposes the manual set for shard() to drop at trace time, but on
    # versions without that introspection the full ambient rules leak
    # through — visible only when a per-rank dim happens to be divisible
    # by the mesh size (e.g. any 2-rank mesh with per-rank batch 4), at
    # which point the partitioner rejects the constraint.  Stripping the
    # manual axes from the ambient rules is the version-independent fix;
    # per-rank the surviving constraints are all-None, exactly what the
    # divisibility check produced on the previously-working shapes.
    body_rules = (without_axes(rules, frozenset(sync_axes))
                  if rules is not None and sync_axes else rules)

    def manual_body(fn):
        def wrapped(*args):
            with use_rules(body_rules):
                return fn(*args)
        return wrapped

    def mean_loss(loss):
        if not sync_axes:
            return loss
        if dt:
            return det.det_mean(loss, sync_axes)
        return PX.psum(loss, sync_axes) / n_sync

    def layout_for(params):
        return make_bucket_layout(params, mesh, bucket_bytes=bucket_bytes,
                                  deterministic=dt)

    def hier_rank(params, batch):
        loss, grads, _ = lg(params, batch)
        if sync_axes:
            grads = jax.tree.map(
                lambda g: hier_all_reduce_mean(
                    g, fast_axis=fast_axis, slow_axis=slow_axis,
                    compress_bits=slow_compress_bits), grads)
        return mean_loss(loss), grads

    def reduce_buckets(gbuckets, residuals):
        """The (optionally pipelined, optionally EF) per-bucket reduce.

        Returns (shards, new_residuals); residuals are ``()`` when error
        feedback is off, so rank functions can pass them through shard_map
        uniformly (an empty pytree needs no specs).
        """
        if ef:
            return bucketing.hier_reduce_bucket_shards(
                gbuckets, fast_axis=fast_axis, slow_axis=slow_axis,
                compress_bits=slow_compress_bits, overlap=overlap,
                residuals=residuals)
        shards = bucketing.hier_reduce_bucket_shards(
            gbuckets, fast_axis=fast_axis, slow_axis=slow_axis,
            compress_bits=slow_compress_bits, overlap=overlap)
        return shards, ()

    def det_reduce(gbuckets, residuals):
        """Deterministic reduce -> (full buckets, gnorm, new_residuals).

        Every rank holds the full meaned buckets; the grad norm is pure
        local arithmetic on them (no collective), so both are bitwise
        mesh-factorization-invariant.
        """
        full, new_res = det.det_reduce_bucket_full(
            gbuckets, sync_axes=sync_axes,
            compress_bits=slow_compress_bits,
            residuals=residuals if ef else None)
        return full, det.det_global_norm(full), new_res

    def bucketed_rank(params, batch, residuals):
        layout = layout_for(params)
        blg = bucketing.make_bucket_loss_and_grad(model, layout,
                                                  accum=accum)
        loss, gbuckets = blg(bucketing.flatten_to_buckets(layout, params),
                             batch)
        if dt:
            full, gnorm, new_res = det_reduce(gbuckets, residuals)
        else:
            shards, new_res = reduce_buckets(gbuckets, residuals)
            gnorm = bucketing.shard_global_norm(shards, fast_axis)
            full = bucketing.all_gather_buckets(shards,
                                                fast_axis=fast_axis)
        grads = bucketing.unflatten_from_buckets(layout, full,
                                                 dtype=jnp.float32)
        return mean_loss(loss), grads, gnorm, new_res

    def zero1_rank(layout, params, state, batch):
        opt_state, residuals = ((state.opt, state.residuals) if ef
                                else (state, ()))
        blg = bucketing.make_bucket_loss_and_grad(model, layout,
                                                  accum=accum)
        # forward from the (replicated) storage params, not from an
        # all-gather of the masters: params are the previous step's
        # gathered masters cast to storage dtype, and the forward casts
        # the buckets to storage dtype anyway, so loss/grads are
        # bit-identical — and the fast tier carries one full-model
        # gather per step (updated params) instead of two
        loss, gbuckets = blg(bucketing.flatten_to_buckets(layout, params),
                             batch)
        if dt:
            full, gnorm, new_res = det_reduce(gbuckets, residuals)
            shards = det.det_fast_shards(full, fast_axis)
        else:
            shards, new_res = reduce_buckets(gbuckets, residuals)
            gnorm = bucketing.shard_global_norm(shards, fast_axis)
        new_state, om = optim.apply_flat(ocfg, shards, opt_state,
                                         gnorm=gnorm)
        new_pb = bucketing.all_gather_buckets(new_state.master,
                                              fast_axis=fast_axis)
        params = bucketing.unflatten_from_buckets(layout, new_pb)
        if ef:
            new_state = EFState(new_state, new_res)
        return params, new_state, {"loss": mean_loss(loss), **om}

    def batch_specs(batch):
        return jax.tree.map(lambda _: P(sync_axes), batch)

    def residual_specs(layout):
        if not ef:
            return ()
        return (_residual_spec(fast_axis, slow_axis),) * layout.n_buckets

    if mode == "hier_bucketed_zero1":
        def step(params, opt_state, batch):
            layout = layout_for(params)
            if not sync_axes:
                return zero1_rank(layout, params, opt_state, batch)
            bspec = P(fast_axis) if fast_axis else P()
            state_specs = optim.BucketedOptState(
                step=P(), mu=(bspec,) * layout.n_buckets,
                nu=(bspec,) * layout.n_buckets,
                master=(bspec,) * layout.n_buckets)
            if ef:
                state_specs = EFState(state_specs, residual_specs(layout))
            pspecs = jax.tree.map(lambda _: P(), params)
            return PX.shard_map(
                manual_body(functools.partial(zero1_rank, layout)),
                mesh=mesh,
                in_specs=(pspecs, state_specs, batch_specs(batch)),
                out_specs=(pspecs, state_specs,
                           {"loss": P(), "lr": P(), "grad_norm": P()}),
                check_vma=False, axis_names=set(sync_axes),
            )(params, opt_state, batch)
        return step

    def step(params, opt_state, batch):
        inner_opt = opt_state.opt if ef else opt_state
        ef_res = opt_state.residuals if ef else ()
        new_res = ()
        if not sync_axes:
            if mode == "hier_bucketed":
                loss, grads, gnorm, new_res = bucketed_rank(
                    params, batch, ef_res)
            else:
                loss, grads = hier_rank(params, batch)
                gnorm = None
        elif mode == "hier_bucketed":
            layout = layout_for(params)
            pspecs = jax.tree.map(lambda _: P(), params)
            rspecs = residual_specs(layout)
            loss, grads, gnorm, new_res = PX.shard_map(
                manual_body(bucketed_rank), mesh=mesh,
                in_specs=(pspecs, batch_specs(batch), rspecs),
                out_specs=(P(), pspecs, P(), rspecs),
                check_vma=False, axis_names=set(sync_axes),
            )(params, batch, ef_res)
        else:
            pspecs = jax.tree.map(lambda _: P(), params)
            loss, grads = PX.shard_map(
                manual_body(hier_rank), mesh=mesh,
                in_specs=(pspecs, batch_specs(batch)),
                out_specs=(P(), pspecs),
                check_vma=False, axis_names=set(sync_axes),
            )(params, batch)
            gnorm = None
        params, inner_opt, om = optim.apply(ocfg, params, grads,
                                            inner_opt, gnorm=gnorm)
        opt_state = EFState(inner_opt, new_res) if ef else inner_opt
        return params, opt_state, {"loss": loss, **om}

    return step


def make_train_step(model, ocfg: optim.AdamWConfig, *, accum: int = 1,
                    rules: Optional[MeshRules] = None,
                    cross_pod_mode: str = "xla",
                    bucket_bytes: int = bucketing.DEFAULT_BUCKET_BYTES,
                    slow_compress_bits: int = 0,
                    overlap: bool = False,
                    slow_error_feedback: bool = False,
                    deterministic_reduce: bool = False):
    """Returns step(params, opt_state, batch) -> (params, opt, metrics).

    In the ``xla`` and ``compressed`` modes ``metrics["counters"]`` holds
    the model's counters (``make_loss_and_grad``), which
    ``Trainer.history`` copies; the manual-sync modes report none.

    ``overlap=True`` (bucketed modes only) software-pipelines the
    per-bucket hierarchical sync: bucket i+1's fast-axis reduce-scatter
    is issued under bucket i's slow hop.  Bitwise-identical losses; a
    no-op on single-bucket layouts and size-1 meshes.

    ``slow_error_feedback=True`` (bucketed modes, requires
    ``slow_compress_bits=8``) carries each rank's int8 quantization
    residual across steps.  The step then takes/returns an
    :class:`EFState` wrapping the optimizer state (build the residuals
    with :func:`init_slow_residuals`).

    ``deterministic_reduce=True`` (bucketed modes) replaces the
    hierarchical schedule with the mesh-factorization-invariant gather +
    fixed-tree fold: the whole step is then bitwise-identical across
    (pod, data) factorizations of the same rank count, so a sharded
    checkpoint reshard-restored onto a repacked mesh continues the exact
    loss curve.  Bandwidth-heavier than the hierarchical schedule (the
    gather moves every rank's contribution) — the
    verification/elasticity schedule, not the throughput one.  Mutually
    exclusive with ``overlap`` (there is no two-tier pipeline to
    overlap); composes with ``slow_compress_bits``/``slow_error_feedback``
    (residuals from ``init_slow_residuals(..., deterministic=True)``).
    """
    if cross_pod_mode not in CROSS_POD_MODES:
        raise ValueError(f"unknown cross_pod_mode {cross_pod_mode!r}; "
                         f"known: {CROSS_POD_MODES}")
    if ((overlap or slow_error_feedback or deterministic_reduce)
            and cross_pod_mode not in BUCKETED_SYNC_MODES):
        raise ValueError(
            f"overlap/slow_error_feedback/deterministic_reduce apply to "
            f"the bucketed sync modes {BUCKETED_SYNC_MODES}, not "
            f"{cross_pod_mode!r}")
    if slow_error_feedback and slow_compress_bits != 8:
        raise ValueError(
            "slow_error_feedback carries int8 quantization residuals; "
            f"it requires slow_compress_bits=8 (got {slow_compress_bits})")
    if deterministic_reduce and overlap:
        raise ValueError(
            "deterministic_reduce has no two-tier pipeline to overlap; "
            "pick one of overlap / deterministic_reduce")
    mesh = rules.mesh if rules is not None else None
    if (cross_pod_mode == "compressed" and mesh is not None
            and "pod" in mesh.axis_names and mesh.shape["pod"] > 1):
        # the partial shard_map (manual 'pod', auto 'data') this mode
        # used fatally aborts XLA on (pod, data) meshes under the pinned
        # jax 0.4.37; the bucketed modes subsume it (same int8 slow hop,
        # fewer collectives), so the mode is a clear error, not a crash
        raise NotImplementedError(
            "cross_pod_mode='compressed' is not supported on multi-pod "
            "meshes (XLA aborts on its partial shard_map under the "
            "pinned jax); use cross_pod_mode='hier_bucketed' with "
            "slow_compress_bits=8 for the int8 cross-pod hop")
    if cross_pod_mode in MANUAL_SYNC_MODES:
        return _make_manual_sync_step(
            model, ocfg, accum=accum, rules=rules, mode=cross_pod_mode,
            bucket_bytes=bucket_bytes,
            slow_compress_bits=slow_compress_bits, overlap=overlap,
            slow_error_feedback=slow_error_feedback,
            deterministic_reduce=deterministic_reduce)
    lg = make_loss_and_grad(model, accum=accum)

    def base_step(params, opt_state, batch):
        loss, grads, counters = lg(params, batch)
        params, opt_state, om = optim.apply(ocfg, params, grads, opt_state)
        metrics = {"loss": loss, **om, "counters": counters}
        return params, opt_state, metrics

    return base_step


def make_jitted_train_step(model, ocfg, *, accum, rules,
                           param_shardings=None, opt_shardings=None,
                           batch_sharding=None, cross_pod_mode="xla",
                           bucket_bytes=bucketing.DEFAULT_BUCKET_BYTES,
                           slow_compress_bits=0, overlap=False,
                           slow_error_feedback=False,
                           deterministic_reduce=False):
    step = make_train_step(model, ocfg, accum=accum, rules=rules,
                           cross_pod_mode=cross_pod_mode,
                           bucket_bytes=bucket_bytes,
                           slow_compress_bits=slow_compress_bits,
                           overlap=overlap,
                           slow_error_feedback=slow_error_feedback,
                           deterministic_reduce=deterministic_reduce)

    def wrapped(params, opt_state, batch):
        with use_rules(rules):
            return step(params, opt_state, batch)

    kw = {}
    if param_shardings is not None:
        kw["in_shardings"] = (param_shardings, opt_shardings,
                              batch_sharding)
        kw["out_shardings"] = (param_shardings, opt_shardings, None)
    if cross_pod_mode in BUCKETED_SYNC_MODES:
        kw["compiler_options"] = bucketing.NO_COMBINE_COMPILER_OPTIONS
    return jax.jit(wrapped, donate_argnums=(0, 1), **kw)


def put_batch(batch: Dict[str, np.ndarray], rules: Optional[MeshRules]
              ) -> Dict[str, jax.Array]:
    """Host batch -> device arrays.  On a mesh the leading (batch) dim
    is split over the rules' batch axes, so each device receives its
    own rows instead of the whole batch landing on the default device
    (replicated instead when the axes do not divide the batch)."""
    if rules is None or rules.mesh is None:
        return {k: jnp.asarray(v) for k, v in batch.items()}
    spec = P(batch_axes(rules))
    return {k: jax.device_put(v, NamedSharding(
                rules.mesh, fit_spec(rules.mesh, np.shape(v), spec)))
            for k, v in batch.items()}


def wrap_ef_state(params, opt_state, opt_shardings, mesh, *,
                  bucket_bytes: int = bucketing.DEFAULT_BUCKET_BYTES,
                  deterministic: bool = False):
    """Wrap an optimizer state (and its shardings, when sharded) with
    zero error-feedback residuals for ``slow_error_feedback=True``."""
    res = init_slow_residuals(params, mesh, bucket_bytes=bucket_bytes,
                              deterministic=deterministic)
    fast_axis, slow_axis = grad_sync_axes(mesh)
    if mesh is not None and (fast_axis or slow_axis):
        rshard = NamedSharding(mesh, _residual_spec(fast_axis, slow_axis))
        res = tuple(jax.device_put(r, rshard) for r in res)
        if opt_shardings is not None:
            opt_shardings = EFState(opt_shardings, (rshard,) * len(res))
    return EFState(opt_state, res), opt_shardings


def init_train_state(model, ocfg: optim.AdamWConfig, *,
                     rules: Optional[MeshRules] = None, seed: int = 0,
                     cross_pod_mode: str = "xla",
                     bucket_bytes: int = bucketing.DEFAULT_BUCKET_BYTES,
                     slow_error_feedback: bool = False,
                     deterministic_reduce: bool = False):
    """Initial ``(params, opt_state, opt_shardings, layout)`` for a mode.

    The single state construction the Trainer, the HLO lint matrix
    (``train_step_hlo``) and the benches share, so the state/layout a
    step function expects cannot drift from what callers build:
    ``hier_bucketed_zero1`` needs the fast-axis-sharded
    :class:`~repro.optim.BucketedOptState` over the *same*
    ``(bucket_bytes, deterministic)`` layout the step derives, and
    ``slow_error_feedback`` wraps it in an :class:`EFState`.
    ``opt_shardings``/``layout`` are None outside the zero1 mode.
    """
    params = model.init(jax.random.key(seed))
    mesh = rules.mesh if rules is not None else None
    opt_shardings = None
    layout = None
    if cross_pod_mode == "hier_bucketed_zero1":
        layout = make_bucket_layout(params, mesh,
                                    bucket_bytes=bucket_bytes,
                                    deterministic=deterministic_reduce)
        opt_state, opt_shardings = init_sharded_zero1(
            ocfg, params, layout, mesh)
    else:
        opt_state = optim.init(ocfg, params)
    if slow_error_feedback:
        opt_state, opt_shardings = wrap_ef_state(
            params, opt_state, opt_shardings, mesh,
            bucket_bytes=bucket_bytes,
            deterministic=deterministic_reduce)
    return params, opt_state, opt_shardings, layout


# ---------------------------------------------------------------------------
# static-analysis hooks
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainStepHlo:
    """Both textual HLO dialects of one lowered+compiled train step.

    No single print carries every statically checkable contract, so the
    lint rules get both: ``lowered_text`` (``lowered.as_text("hlo")``,
    pre-optimization) holds the ``buffer_donor`` donation offers and the
    ``opt-barrier`` ops the backend consumes before scheduling;
    ``compiled_text`` (``compiled.as_text()``, post-optimization) holds
    the realized ``input_output_alias`` pairs, the scheduled collective
    mix and ``known_trip_count`` loop annotations.
    """

    lowered_text: str
    compiled_text: str
    n_buckets: int                 # 0 for the non-bucketed modes
    donated_args: int              # leaves in the donated (params, opt)
    grad_bytes: int                # total f32 gradient bytes per step


def train_step_hlo(model, ocfg: optim.AdamWConfig, *, rules: MeshRules,
                   accum: int = 1, seed: int = 0, batch_size: int = 8,
                   seq_len: int = 16, cross_pod_mode: str = "xla",
                   bucket_bytes: int = bucketing.DEFAULT_BUCKET_BYTES,
                   slow_compress_bits: int = 0, overlap: bool = False,
                   slow_error_feedback: bool = False,
                   deterministic_reduce: bool = False) -> TrainStepHlo:
    """Lower + compile one train step and return its HLO (both dialects).

    The hook behind ``scripts/lint_hlo.py``: builds the real initial
    state via :func:`init_train_state` (so the lowered program is the
    one training runs, donation and all) on a synthetic tokens/targets
    batch, and captures the pre- and post-optimization prints.
    """
    params, opt_state, _, layout = init_train_state(
        model, ocfg, rules=rules, seed=seed,
        cross_pod_mode=cross_pod_mode, bucket_bytes=bucket_bytes,
        slow_error_feedback=slow_error_feedback,
        deterministic_reduce=deterministic_reduce)
    mesh = rules.mesh if rules is not None else None
    if layout is None and cross_pod_mode in BUCKETED_SYNC_MODES:
        layout = make_bucket_layout(params, mesh,
                                    bucket_bytes=bucket_bytes,
                                    deterministic=deterministic_reduce)
    batch = {"tokens": jnp.zeros((batch_size, seq_len), jnp.int32),
             "targets": jnp.zeros((batch_size, seq_len), jnp.int32)}
    step = make_jitted_train_step(
        model, ocfg, accum=accum, rules=rules,
        cross_pod_mode=cross_pod_mode, bucket_bytes=bucket_bytes,
        slow_compress_bits=slow_compress_bits, overlap=overlap,
        slow_error_feedback=slow_error_feedback,
        deterministic_reduce=deterministic_reduce)
    if mesh is not None:
        with mesh:
            lowered = step.lower(params, opt_state, batch)
    else:
        lowered = step.lower(params, opt_state, batch)
    compiled = lowered.compile()
    return TrainStepHlo(
        lowered_text=lowered.as_text("hlo"),
        compiled_text=compiled.as_text(),
        n_buckets=layout.n_buckets if layout is not None else 0,
        donated_args=len(jax.tree.leaves((params, opt_state))),
        grad_bytes=sum(4 * int(np.prod(p.shape))
                       for p in jax.tree.leaves(params)))


# ---------------------------------------------------------------------------
# fault-tolerant loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainerConfig:
    n_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    log_every: int = 10
    accum: int = 1
    async_ckpt: bool = True
    cross_pod_mode: str = "xla"
    bucket_bytes: int = bucketing.DEFAULT_BUCKET_BYTES
    slow_compress_bits: int = 0
    overlap: bool = False
    slow_error_feedback: bool = False
    deterministic_reduce: bool = False
    # sharded (per-rank shard + manifest) checkpoint format; False falls
    # back to the legacy gathered per-leaf format (repro.checkpoint)
    save_sharded: bool = True
    # recovery knobs (repro.faults): bounded exponential-backoff retries
    # for transient I/O during checkpoint save/restore, and whether a
    # corrupt committed step at resume is quarantined on disk with
    # fallback to the previous committed step (RecoveryReport returned
    # in the run output) instead of raising
    max_restore_retries: int = 0
    fallback_on_corrupt: bool = False


class Trainer:
    def __init__(self, model, ocfg: optim.AdamWConfig,
                 tcfg: TrainerConfig, data_cfg: DataConfig, *,
                 rules: Optional[MeshRules] = None,
                 failure_hook: Optional[Callable[[int], bool]] = None):
        self.model = model
        self.ocfg = ocfg
        self.tcfg = tcfg
        self.data_cfg = data_cfg
        self.rules = rules
        self.failure_hook = failure_hook
        self.straggler = StragglerDetector()
        self.step_fn = make_jitted_train_step(
            model, ocfg, accum=tcfg.accum, rules=rules,
            cross_pod_mode=tcfg.cross_pod_mode,
            bucket_bytes=tcfg.bucket_bytes,
            slow_compress_bits=tcfg.slow_compress_bits,
            overlap=tcfg.overlap,
            slow_error_feedback=tcfg.slow_error_feedback,
            deterministic_reduce=tcfg.deterministic_reduce)
        self.history: list = []

    def _init_state(self, seed: int = 0):
        params, opt_state, self._opt_shardings, self._layout = \
            init_train_state(
                self.model, self.ocfg, rules=self.rules, seed=seed,
                cross_pod_mode=self.tcfg.cross_pod_mode,
                bucket_bytes=self.tcfg.bucket_bytes,
                slow_error_feedback=self.tcfg.slow_error_feedback,
                deterministic_reduce=self.tcfg.deterministic_reduce)
        return params, opt_state

    def run(self, *, seed: int = 0, resume: bool = True
            ) -> Dict[str, Any]:
        # sharding constraints inside the jitted step trace against the
        # ambient mesh context; without it any --data-parallel launch
        # fails at first trace (tests enter the mesh themselves, which
        # is why only the launcher path ever hit this)
        mesh = self.rules.mesh if self.rules is not None else None
        if mesh is not None:
            with mesh:
                return self._run(seed=seed, resume=resume)
        return self._run(seed=seed, resume=resume)

    def _restore_policy(self, params, opt_state):
        """Per-leaf shape-mismatch policy for reshard-on-restore.

        Flat ZeRO-1 buckets (masters/moments) tolerate padded-size
        drift between mesh factorizations (PAD_FLAT: the tail past the
        live prefix is zeros on both sides); hierarchical EF residuals
        whose global size follows the pod count are re-zeroed (ZERO —
        deterministic-mode residuals are rank-count-keyed, so their
        shapes match and restore exactly); everything else must match
        exactly.
        """
        exact = functools.partial(jax.tree.map, lambda _: ckpt_lib.EXACT)

        def opt_policy(o):
            if isinstance(o, optim.BucketedOptState):
                nb = len(o.master)
                return optim.BucketedOptState(
                    step=ckpt_lib.EXACT,
                    mu=(ckpt_lib.PAD_FLAT,) * nb,
                    nu=(ckpt_lib.PAD_FLAT,) * nb,
                    master=(ckpt_lib.PAD_FLAT,) * nb)
            return exact(o)

        if isinstance(opt_state, EFState):
            pol = EFState(opt_policy(opt_state.opt),
                          (ckpt_lib.ZERO,) * len(opt_state.residuals))
        else:
            pol = opt_policy(opt_state)
        return (exact(params), pol)

    def _save(self, pending, step: int, params, opt_state, mesh):
        """Join the previous save, start checkpoint ``step``; returns
        the new save's handle."""
        if pending is not None:
            pending.join()
        sdir = ckpt_lib.step_dir(self.tcfg.ckpt_dir, step)
        if self.tcfg.save_sharded:
            return ckpt_lib.save_sharded(
                sdir, step, (params, opt_state), layout=self._layout,
                mesh=mesh, blocking=not self.tcfg.async_ckpt)
        return legacy_ckpt.save(sdir, step, (params, opt_state),
                                blocking=not self.tcfg.async_ckpt)

    def _run(self, *, seed: int, resume: bool) -> Dict[str, Any]:
        from repro.faults.recovery import restore_with_fallback
        from repro.faults.retry import RetryPolicy
        tcfg = self.tcfg
        start = 0
        recovery = None
        retry = RetryPolicy(max_retries=tcfg.max_restore_retries)
        with tracing.span("train.init_state"):
            params, opt_state = self._init_state(seed)
        mesh = self.rules.mesh if self.rules is not None else None
        last = ckpt_lib.latest_step(tcfg.ckpt_dir) if resume else None
        if last is not None:
            with tracing.span("train.restore"):
                # restore the zero1 state straight onto its fast-axis
                # shards — an unsharded restore would replicate the full
                # f32 masters on every device until the first step
                shardings = ((None, self._opt_shardings)
                             if self._opt_shardings is not None else None)
                policy = self._restore_policy(params, opt_state)
                if tcfg.fallback_on_corrupt:
                    start, (params, opt_state), recovery = \
                        restore_with_fallback(
                            tcfg.ckpt_dir, (params, opt_state),
                            shardings=shardings, policy=policy,
                            layout=self._layout, retry=retry)
                else:
                    start, (params, opt_state) = ckpt_lib.restore_auto(
                        ckpt_lib.step_dir(tcfg.ckpt_dir, last),
                        (params, opt_state), shardings=shardings,
                        policy=policy, layout=self._layout, retry=retry)
        corpus = SyntheticCorpus(self.data_cfg)
        prefetch = Prefetcher(corpus, start_step=start)
        pending = None
        try:
            for step in range(start, tcfg.n_steps):
                if self.failure_hook and self.failure_hook(step):
                    raise RuntimeError(f"injected failure at step {step}")
                with tracing.step_span("train.step", step):
                    with tracing.span("train.data_wait") as wait:
                        _, batch = prefetch.next()
                    with tracing.span("train.put_batch"):
                        batch = put_batch(batch, self.rules)
                    with tracing.span("train.dispatch"):
                        out = self.step_fn(params, opt_state, batch)
                    with tracing.span("train.device_wait") as done:
                        params, opt_state, metrics = \
                            jax.block_until_ready(out)
                    # data wait through device wait: the step as the
                    # host sees it, without readback or checkpoint
                    dt = (done.end_ns - wait.start_ns) * 1e-9
                    with tracing.span("train.readback"):
                        self.straggler.record(dt)
                        if step % tcfg.log_every == 0:
                            self.history.append(
                                {"step": step,
                                 "loss": float(metrics["loss"]),
                                 "sec_per_step": dt,
                                 **{k: int(v) for k, v in jax.device_get(
                                     metrics.get("counters", {})).items()}})
                    if (step + 1) % tcfg.ckpt_every == 0:
                        with tracing.span("train.ckpt"):
                            pending = self._save(pending, step + 1,
                                                 params, opt_state, mesh)
        finally:
            if pending is not None:
                pending.join()
            prefetch.close()
        return {"params": params, "opt_state": opt_state,
                "history": self.history,
                "stragglers": self.straggler.summary(),
                "recovery": recovery}
