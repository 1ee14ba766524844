"""Trainer: the orchestration layer.

TPU-native replacement for the reference's ``dist_train(args)``
(dataParallelTraining_NN_MPI.py:56-236, SURVEY.md C2): world/mesh formation,
dataset build, deterministic replicated init, sharded loading, the jitted
epoch/step loop, and per-epoch loss reporting — with checkpoint/resume,
structured metrics and profiling as extensions (SURVEY.md §5 notes all of
those are absent in the reference).
"""

from __future__ import annotations

import sys
import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from ..config import TrainConfig
from ..data.datasets import build_dataset
from ..data.loader import ShardedLoader
from ..models.registry import build_model
from ..ops import optim as optim_lib
from ..ops import schedules
from ..parallel import data_parallel as dp
from ..parallel.mesh import describe, make_mesh, world_setup
from ..utils import compile_ledger as ledger_lib
from ..utils import profiling, prng
from ..utils.logging import MetricsLogger, Throughput, is_leader, log
from . import telemetry as telemetry_lib
from . import trace as trace_lib
from .state import TrainState


class Trainer:
    def __init__(self, cfg: TrainConfig, mesh=None, data=None):
        # --param_dtype: the training-job spelling of the model's param
        # storage dtype (bf16 params halve HBM + the sharded-update
        # all-gather bytes; pair with --master_weights for f32 update
        # math).  Applied to the model config HERE so every downstream
        # consumer — model init, checkpoint templates, FLOPs accounting —
        # sees one consistent dtype.
        if cfg.param_dtype:
            import dataclasses as _dc

            if cfg.param_dtype not in ("float32", "bfloat16", "float16"):
                raise ValueError(
                    f"unknown --param_dtype {cfg.param_dtype!r} "
                    "(choices: float32, bfloat16, float16)")
            cfg = _dc.replace(cfg, model=_dc.replace(
                cfg.model, dtype=cfg.param_dtype))
        self.cfg = cfg
        world_setup()
        # capacity floor (DESIGN.md §10): a world below --min_devices must
        # not train at all — exit 46 (no-retry) instead of running a
        # degraded job the operator said is too small to be useful
        if cfg.min_devices and jax.device_count() < cfg.min_devices:
            from .resilience import CapacityAbort

            raise CapacityAbort(
                f"{jax.device_count()} healthy device(s) < --min_devices "
                f"{cfg.min_devices}: refusing to train below the capacity "
                "floor (exit 46; raise capacity or lower --min_devices)")
        if cfg.collective_timeout > 0:
            from ..parallel import distributed

            distributed.set_collective_timeout(cfg.collective_timeout)
        self.mesh = mesh if mesh is not None else make_mesh(cfg.mesh)
        self.seq_parallel = self.mesh.shape.get("seq", 1) > 1
        self.pipeline = self.mesh.shape.get("pipe", 1) > 1
        self.expert = self.mesh.shape.get("expert", 1) > 1
        self.tensor = self.mesh.shape.get("tensor", 1) > 1
        # strategy -> step builder:
        #   pipe (x tensor)      -> parallel.pipeline shard_map (explicit
        #                           Megatron TP inside the stages, DP x TP x PP)
        #   tensor/fsdp (no pipe)-> parallel.gspmd (jit + annotations)
        #   seq                  -> parallel.spmd shard_map (ring attention)
        #   expert               -> parallel.expert shard_map (all_to_all)
        #   seq x tensor        -> parallel.spmd sp_tp shard_map (Megatron
        #                          matmuls + ring/ulysses attention)
        #   expert x tensor     -> parallel.expert moe_tp shard_map (Megatron
        #                          attention + tensor-sharded experts);
        #                          x seq runs seq-sharded attention too, and
        #                          seq x tensor with an MoE FFN rides the
        #                          same step with the expert axis at 1
        #   seq x expert        -> parallel.expert shard_map with seq_axis
        #                          (ring attention + all_to_all experts)
        fsdp_on = self.mesh.shape.get("fsdp", 1) > 1
        moe_model = cfg.model.moe_experts > 0
        self.sp_tp = (self.seq_parallel and self.tensor
                      and not (self.pipeline or self.expert or fsdp_on
                               or moe_model))
        # (SP x) EP x TP: Megatron attention + tensor-sharded experts,
        # optionally with seq-sharded attention over 'seq'.  SP x TP with
        # an MoE FFN rides this path too, with the expert axis at 1
        # (experts held whole, hidden dim tensor-sharded — no all_to_all).
        self.ep_tp = (self.tensor and not (self.pipeline or fsdp_on)
                      and (self.expert
                           or (self.seq_parallel and moe_model)))
        self.sp_ep = (self.seq_parallel and self.expert
                      and not (self.pipeline or self.tensor or fsdp_on))
        # DP x PP x EP (x SP x TP): the pipeline step threads the MoE aux
        # loss through the tick carry and runs the all_to_all dispatch
        # inside each stage (tensor > 1 additionally Megatron-shards
        # attention heads and each expert's hidden dim — GShard in the
        # pipeline; seq > 1 seq-shards each stage's attention)
        self.pp_ep = (self.pipeline and self.expert and not fsdp_on)
        # DP x PP x SP (x TP/EP): each stage's attention rings over 'seq'
        # while activations rotate over 'pipe' — long-context pipelining,
        # composing with Megatron TP and expert parallelism (round 4)
        self.pp_sp = (self.pipeline and self.seq_parallel and not fsdp_on)
        self.gspmd = (not self.pipeline and not self.sp_tp and not self.ep_tp
                      and (self.tensor or fsdp_on))
        unwired = [name for name, on in
                   (("seq", self.seq_parallel and not self.pp_sp),
                    ("fsdp", fsdp_on),
                    ("expert", self.expert and not self.pp_ep)) if on]
        if self.pipeline and unwired:
            raise NotImplementedError(
                f"pipe composes with the data, tensor, expert (MoE), and "
                f"seq (seq-sharded attention) axes in any mix; got pipe x "
                f"{unwired} — fsdp's parameter sharding is the GSPMD "
                "path's job (compose parallel.* step builders directly)")
        exclusive = [name for name, on in
                     (("seq", self.seq_parallel and not self.sp_tp
                       and not self.sp_ep and not self.ep_tp
                       and not self.pp_sp),
                      ("tensor/fsdp", self.gspmd),
                      ("expert", self.expert and not self.ep_tp
                       and not self.sp_ep and not self.pp_ep)) if on]
        if len(exclusive) > 1:
            raise NotImplementedError(
                f"wired combinations: one of seq/tensor/fsdp/expert alone, "
                f"pipe x tensor, seq x tensor, seq x expert, expert x "
                f"tensor, or seq x expert x tensor (all x data); got "
                f"{exclusive} — compose parallel.* step builders directly "
                "for other mixes")
        if self.pipeline and cfg.model.arch != "transformer":
            raise ValueError("pipe axis > 1 requires the transformer model")
        if self.expert and (cfg.model.arch != "transformer"
                            or cfg.model.moe_experts <= 0):
            raise ValueError("expert axis > 1 requires a transformer with "
                             "moe_experts > 0 (--moe_experts)")
        if cfg.grad_reduction not in ("global_mean", "per_shard_mean"):
            # 'local' exists in data_parallel.make_train_step ONLY as a
            # collective-cost ablation — replicas silently
            # diverge; it must never reach a training job (the CLI choices
            # already exclude it; this guards programmatic configs too)
            raise ValueError(
                f"grad_reduction={cfg.grad_reduction!r} is not a training "
                "semantic (choices: global_mean, per_shard_mean)")
        if ((self.pipeline or self.expert or self.sp_tp or self.ep_tp)
                and cfg.grad_reduction != "global_mean"):
            raise ValueError("pipeline/expert/seq-x-tensor steps always use "
                             "global_mean gradient semantics")
        # (expert x tensor's attention/divisibility invariants live in
        # parallel.expert._validate_moe_tp — the single consult point,
        # called by both step builders)
        if cfg.vocab_parallel and not self.sp_tp:
            raise ValueError(
                "--vocab_parallel shards the embedding/head over 'tensor' "
                "on the seq x tensor path (--sp > 1 and --tp > 1); other "
                "layouts keep them replicated")
        if cfg.model.ce_chunk > 0:
            # only data_parallel.make_loss_fn consults the model's
            # fused_loss_sum hook; anywhere it cannot fire the flag would
            # be silently ignored and the full (B, T, vocab) logits
            # materialized anyway — fail loudly instead (the TP paths get
            # the same memory relief from --vocab_parallel's sharded head)
            if not self.pipeline and (self.tensor or self.expert
                                      or fsdp_on):
                # wired: pure DP/ZeRO-1, DP x SP, and every pipeline
                # layout (the pipeline head is replicated, so its last
                # stage fuses the same way).  Not wired: the non-pipeline
                # tensor/expert/fsdp step builders — there the head is
                # (or may be) sharded and --vocab_parallel is the
                # equivalent relief.
                raise ValueError(
                    "--ce_chunk (fused chunked cross-entropy) is wired on "
                    "the data-parallel/ZeRO-1, sequence-parallel, and "
                    "pipeline step paths; with non-pipeline tp/ep/fsdp "
                    "axes use --vocab_parallel (seq x tensor) or drop "
                    "--ce_chunk")
            if (cfg.model.arch != "transformer"
                    or cfg.loss.partition("@")[0] != "cross_entropy"):
                raise ValueError(
                    "--ce_chunk fuses the transformer LM head into "
                    "cross-entropy; it does nothing for "
                    f"arch={cfg.model.arch!r} loss={cfg.loss!r} — drop it")
        if (cfg.optimizer == "adafactor"
                and (self.pipeline or self.sp_tp or self.expert
                     or self.ep_tp
                     or cfg.update_sharding in ("zero1", "sharded"))):
            raise ValueError(
                "adafactor's stats are exact only where every leaf sees its "
                "full matrix: DP/SP shard_map layouts and GSPMD global-view. "
                "Layouts that slice inside matrices (pipe, seq x tensor, "
                "expert x tensor) make the factor means shard-local; the "
                "expert axis slices the stacked-expert leaves, so the "
                "update-RMS clip / parameter-scale RMS(p) (whole-leaf "
                "means) and the (E, f) bias column factor become "
                "EP-degree-dependent; zero1's flat state cannot carry "
                "factored stats at all, and the per-leaf sharded update "
                "scatters inside matrices the same way. Use "
                "adam/adamw/lion/sgd there")
        from ..parallel.sequence import SEQ_SHARDED_IMPLS

        if (cfg.model.arch == "transformer"
                and cfg.model.attention in SEQ_SHARDED_IMPLS
                and not self.seq_parallel):
            raise ValueError(
                f"attention={cfg.model.attention!r} needs the 'seq' mesh "
                "axis > 1 (--sp); use dense or flash on an unsharded "
                "sequence")
        self.zero1 = cfg.update_sharding == "zero1"
        self.sharded = cfg.update_sharding == "sharded"
        if self.zero1 and (self.gspmd or self.pipeline or self.expert
                           or self.sp_tp or self.ep_tp):
            raise NotImplementedError(
                "update_sharding='zero1' is the flat-buffer shard_map DP "
                "and DP x seq layout; the automatic per-leaf form "
                "(update_sharding='sharded') covers the GSPMD path too")
        if self.sharded and (self.pipeline or self.expert or self.sp_tp
                             or self.ep_tp):
            raise NotImplementedError(
                "update_sharding='sharded' is wired into the shard_map DP "
                "/ DP x seq and GSPMD (tensor/fsdp) layouts; the "
                "pipe/expert/seq-x-tensor layouts own their slicing")
        if (self.zero1 or self.sharded) and cfg.grad_reduction != "global_mean":
            raise ValueError(f"update_sharding={cfg.update_sharding!r} "
                             "implies global_mean gradient semantics")
        if cfg.master_weights and not self.sharded:
            raise ValueError(
                "--master_weights keeps the f32 master copy in the SHARDED "
                "optimizer state (1/N per replica); it requires "
                "update_sharding='sharded' — a replicated master would "
                "duplicate param memory instead of saving it")
        mm = cfg.model.matmul_dtype
        if mm not in ("bf16", "int8", "fp8"):
            raise ValueError(f"unknown --matmul_dtype {mm!r} "
                             "(choices: bf16, int8, fp8)")
        if mm != "bf16":
            # quantized-matmul seam (ops.qmm, DESIGN.md §14): wired where
            # the model's own forward runs whole matmuls — the DP /
            # DP x seq shard_map and GSPMD layouts (all update_sharding
            # forms; the global-norm/guard/metrics seam rides unchanged).
            # The explicit-TP layouts (pipe, seq x tensor, expert x
            # tensor) slice matmuls in their own block code and would
            # silently bypass the seam — refuse instead.
            if cfg.model.arch != "transformer":
                raise ValueError(
                    f"--matmul_dtype {mm} is the transformer's quantized "
                    "dense-projection seam; it does nothing for "
                    f"arch={cfg.model.arch!r}")
            if (self.pipeline or self.expert or self.sp_tp or self.ep_tp):
                raise NotImplementedError(
                    f"--matmul_dtype {mm} is wired on the DP, DP x seq "
                    "and GSPMD (tensor/fsdp) layouts; the pipe/expert/"
                    "seq-x-tensor layouts run their own sliced matmuls "
                    "outside the ops.qmm seam")
            if cfg.model.moe_experts > 0:
                raise ValueError(
                    f"--matmul_dtype {mm} covers the dense projections "
                    "(qkv/attn_out/ffn/head); the MoE expert einsums are "
                    "not routed through the seam — drop --moe_experts")
        if mm == "fp8" and cfg.model.ce_chunk > 0:
            raise ValueError(
                "--matmul_dtype fp8 needs the delayed-scaling amax "
                "observations, which do not thread the --ce_chunk fused "
                "scan; use int8/bf16 with --ce_chunk, or drop it")
        if cfg.pp_interleave > 1 and not self.pipeline:
            raise ValueError("--pp_interleave needs the pipeline layout "
                             "(--pp > 1); it schedules virtual stage-slices "
                             "per pipeline device")
        if cfg.hang_timeout and not cfg.log_every:
            raise ValueError(
                "--hang_timeout needs log_every > 0: the periodic loss "
                "device_get is the loop's only blocking point, and without "
                "it async dispatch would keep patting the watchdog while "
                "the device is wedged")
        if self.gspmd and cfg.grad_reduction != "global_mean":
            raise ValueError(
                "grad_reduction='per_shard_mean' (the reference's :188-197 "
                "semantics) is only available on the pure-DP shard_map path; "
                "GSPMD global semantics always compute the exact global mean")
        if cfg.model.scan_layers and (self.pipeline or self.gspmd
                                      or self.expert):
            raise ValueError(
                "scan_layers stacks blocks for a depth-independent compile "
                "on the plain DP / DP x seq / seq x tensor paths; the "
                "pipeline/GSPMD/expert layouts own their own stacking and "
                "sharding")
        self.model = build_model(cfg.model)
        if self.seq_parallel and cfg.model.arch != "transformer":
            raise ValueError("seq axis > 1 requires the transformer model")
        self.data = data if data is not None else build_dataset(cfg.data)
        self.val_data: Optional[Dict[str, np.ndarray]] = None
        if cfg.data.val_fraction > 0:
            from ..data.datasets import train_val_split

            self.data, val = train_val_split(self.data,
                                             cfg.data.val_fraction, cfg.seed)
            self.val_data = val or None
        # the expert axis carries batch rows too (parallel.expert layout);
        # the ep_tp path's step specs always include it (size-1 is free)
        self.batch_axes = (("data", "fsdp", "expert")
                           if (self.expert or self.ep_tp)
                           else ("data", "fsdp"))
        # elastic preflight (DESIGN.md §10): an elastic resume whose
        # checkpoint was saved by a DIFFERENT dp width applies the batch
        # policy BEFORE the loader/schedule/step builders are constructed,
        # so every downstream consumer sees the adjusted config
        self._topology_change = None
        self._restored_world = None
        # maps the CONTINUING global step counter onto this loader's
        # (epoch, in-epoch) position after an elastic batch-size change:
        # position_steps = step + _step_offset (0 except on that path);
        # _resume_plan keeps the (epoch, in-epoch step) the offset maps to
        self._step_offset = 0
        self._resume_plan = None
        # newest step this process has committed a snapshot for (gates
        # the redundant final re-save of an end-of-run boundary step)
        self._last_saved_step = None
        cfg = self.cfg = self._elastic_preflight(cfg)
        # striped attention: tokens reorder round-robin over the seq shards
        # (balanced causal blocks — parallel.sequence.striped_permutation);
        # the loader applies the permutation to inputs AND targets, so the
        # per-token training loss is identical to the contiguous layout
        self.seq_permutation = None
        if (self.seq_parallel and cfg.model.arch == "transformer"
                and cfg.model.attention in ("striped", "striped_flash")):
            from ..parallel.sequence import striped_permutation

            self.seq_permutation = striped_permutation(
                cfg.data.seq_len, int(self.mesh.shape["seq"]))
        self.loader = ShardedLoader(
            self.mesh, self.data, cfg.batch_size, shuffle=cfg.shuffle,
            seed=cfg.seed, full_batch=cfg.full_batch,
            remainder=cfg.data.remainder,
            seq_axis="seq" if self.seq_parallel else None,
            batch_axes=self.batch_axes,
            backend=cfg.data.backend,
            seq_permutation=self.seq_permutation)
        if int(cfg.steps_per_dispatch) > 1 and self.loader.multi_host:
            # fail here, not lazily on the first epoch_groups iteration
            # after step-builder compilation (ADVICE r5)
            raise NotImplementedError(
                "steps_per_dispatch > 1 is single-host for now: the "
                "stacked group would need a make_global_batch variant "
                "assembling per-process rows under the scan axis")
        # schedule domain: optimizer steps = train steps (accumulation is
        # inside the step), known once the loader fixes steps-per-epoch
        lr = schedules.make(
            cfg.lr_schedule, cfg.lr,
            total_steps=cfg.nepochs * max(self.loader.steps_per_epoch, 1),
            warmup_steps=cfg.warmup_steps, min_lr=cfg.min_lr)
        # pipeline/expert/zero1 steps clip inside the step (their grad
        # leaves are axis-sharded; optim.with_clipping's shard-local norm
        # would be wrong there — see make_pipeline_train_step /
        # make_moe_train_step / zero1_shard_update)
        if cfg.label_smoothing and cfg.loss != "cross_entropy":
            raise ValueError("--label_smoothing applies to cross_entropy "
                             f"only, not {cfg.loss!r}")
        if not 0.0 <= cfg.label_smoothing < 1.0:
            raise ValueError(
                f"label_smoothing must be in [0, 1), got "
                f"{cfg.label_smoothing} (s >= 1 puts non-positive weight "
                "on the gold class; s < 0 silently disables smoothing)")
        # smoothing applies to the TRAIN loss only; eval reports the
        # unsmoothed loss (ops.losses.get's "@s" suffix form keeps every
        # step builder a plain loss_name consumer)
        train_loss = (f"{cfg.loss}@{cfg.label_smoothing}"
                      if cfg.label_smoothing else cfg.loss)
        step_clips = (self.pipeline or self.expert or self.zero1
                      or self.sp_tp or self.ep_tp
                      or (self.sharded and not self.gspmd))
        self.optimizer = optim_lib.make(
            cfg.optimizer, lr, cfg.momentum, cfg.weight_decay,
            grad_clip=0.0 if step_clips else cfg.grad_clip)
        # mixed-precision master weights (ops.optim.with_master_weights):
        # wrapped INSIDE the guard so a skipped step is a no-op on the
        # master too; the f32 master lands in the sharded opt state, 1/N
        # per replica (validated sharded-only above)
        if cfg.master_weights:
            self.optimizer = optim_lib.with_master_weights(self.optimizer)
        # guarded update (train.resilience / DESIGN.md §6): reject
        # non-finite or over-threshold steps inside the jitted step.  Wired
        # wherever the skip predicate is identical on every replica: the
        # plain DP, DP x SP, and GSPMD layouts (fully-reduced or
        # global-view gradients) AND the sharded-update layouts
        # (zero1/'sharded'), which psum the shard squares into the global
        # norm inside the step and hand it to the guard via
        # Optimizer.update_with_norm.  The remaining sliced layouts
        # (pipeline stages, expert/tensor slicing) have no such norm seam
        # and stay refused.
        self.guarded = cfg.skip_nonfinite or cfg.skip_threshold > 0
        if self.guarded:
            if self.pipeline or self.expert or self.sp_tp or self.ep_tp:
                raise NotImplementedError(
                    "--skip-nonfinite/--skip_threshold (the guarded "
                    "update) is wired into the plain DP, DP x seq, GSPMD "
                    "and sharded-update (zero1/'sharded') layouts; "
                    "pipe/expert/seq-x-tensor updates run on gradient "
                    "slices where a shard-local norm would desynchronize "
                    "the skip decision")
            self.optimizer = optim_lib.with_skip_guard(
                self.optimizer, cfg.skip_threshold)
        # on-device telemetry metrics (train.telemetry, DESIGN.md §7):
        # wired exactly where the skip guard is wired — fully-reduced
        # (DP / DP x SP shard_map), global-view (GSPMD), or sharded-update
        # (zero1/'sharded', one extra scalar psum for the global grad
        # norm).  The remaining sliced layouts (pipe/expert/seq-x-tensor)
        # fall back to the loss-only telemetry stream.
        self.telemetry_metrics = bool(
            cfg.telemetry_dir and cfg.metrics_every > 0
            and not (self.pipeline or self.expert or self.sp_tp
                     or self.ep_tp))
        # per-leaf update-sharding plan (parallel.update_sharding): shape-
        # only, derived once from the model's abstract init — the shard_map
        # step builders need it for their opt-state specs (the GSPMD path
        # derives its own NamedShardings from the param specs instead)
        self.update_plan = None
        if self.sharded and not self.gspmd:
            from ..parallel import update_sharding as us_lib

            dummy = jax.eval_shape(
                lambda: self.model.init(prng.init_key(cfg.seed)))
            self.update_plan = us_lib.plan_updates(
                dummy, dp.data_axis_size(self.mesh))
        exchange_options: Dict[str, Any] = {}  # the plain-DP branch's only
        if self.pipeline:
            from ..parallel import pipeline as pp

            # accumulation folds into the GPipe schedule: accum_steps x
            # more microbatches per step (smaller microbatches, same
            # single optimizer update — and a smaller bubble fraction)
            n_stages = int(self.mesh.shape["pipe"])
            self.train_step = pp.make_pipeline_train_step(
                self.model, self.optimizer, self.mesh, loss_name=train_loss,
                n_microbatches=n_stages * cfg.accum_steps,
                grad_clip=cfg.grad_clip, interleave=cfg.pp_interleave)
            # eval runs the ring schedule forward-only on the pipe-sharded
            # params in place — multi-host safe, no host gather
            # natural microbatch count: accumulation is a gradient-only
            # concept — folding accum_steps in here would only add padding
            # waste on small validation batches
            self.eval_step = pp.make_pipeline_eval_step(
                self.model, self.mesh, loss_name=cfg.loss,
                with_accuracy=(cfg.loss == "cross_entropy"),
                interleave=cfg.pp_interleave)
        elif self.ep_tp:
            from ..parallel import expert as ep_lib

            moe_seq = "seq" if self.seq_parallel else None
            # the ledger seam wraps the INNER jitted program (the outer
            # train_step is a plain closure the seam cannot lower)
            moe_step = ledger_lib.instrument(
                ep_lib.make_moe_tp_train_step(
                    self.model, self.optimizer, self.mesh,
                    loss_name=train_loss, grad_clip=cfg.grad_clip,
                    accum_steps=cfg.accum_steps, seq_axis=moe_seq),
                "train_step[ep_tp]")

            def train_step(state, batch):
                state, metrics = moe_step(state, batch)
                return state, metrics["loss"]

            self.train_step = train_step
            self.eval_step = ep_lib.make_moe_tp_eval_step(
                self.model, self.mesh, loss_name=cfg.loss,
                with_accuracy=(cfg.loss == "cross_entropy"),
                seq_axis=moe_seq)
        elif self.expert:
            from ..parallel import expert as ep_lib

            moe_seq = "seq" if self.sp_ep else None
            moe_step = ledger_lib.instrument(
                ep_lib.make_moe_train_step(
                    self.model, self.optimizer, self.mesh,
                    loss_name=train_loss, grad_clip=cfg.grad_clip,
                    accum_steps=cfg.accum_steps, seq_axis=moe_seq),
                "train_step[expert]")

            def train_step(state, batch):
                state, metrics = moe_step(state, batch)
                return state, metrics["loss"]

            self.train_step = train_step
            self.eval_step = ep_lib.make_moe_eval_step(
                self.model, self.mesh, loss_name=cfg.loss,
                with_accuracy=(cfg.loss == "cross_entropy"),
                seq_axis=moe_seq)
        elif self.sp_tp:
            from ..parallel import spmd

            example = next(iter(self.loader.epoch(0)))
            self.train_step = spmd.make_sp_tp_train_step(
                self.model, self.optimizer, self.mesh, loss_name=train_loss,
                seq_axis="seq", attention_impl=cfg.model.attention,
                example_batch=example, accum_steps=cfg.accum_steps,
                grad_clip=cfg.grad_clip,
                vocab_parallel=cfg.vocab_parallel)
            self.eval_step = spmd.make_sp_tp_eval_step(
                self.model, self.mesh, loss_name=cfg.loss,
                with_accuracy=(cfg.loss == "cross_entropy"),
                seq_axis="seq", attention_impl=cfg.model.attention,
                example_batch=example,
                vocab_parallel=cfg.vocab_parallel)
        elif self.seq_parallel:
            from ..parallel import spmd

            example = next(iter(self.loader.epoch(0)))
            self.train_step = spmd.make_spmd_train_step(
                self.model, self.optimizer, self.mesh, loss_name=train_loss,
                seq_axis="seq", example_batch=example,
                accum_steps=cfg.accum_steps,
                update_sharding=cfg.update_sharding,
                grad_clip=cfg.grad_clip if step_clips else 0.0,
                with_metrics=self.telemetry_metrics,
                update_plan=self.update_plan)
            self.eval_step = dp.make_eval_step(
                self.model, self.mesh, loss_name=cfg.loss,
                with_accuracy=(cfg.loss == "cross_entropy"),
                seq_axis="seq")
        elif self.gspmd:
            from ..parallel import gspmd

            example = next(iter(self.loader.epoch(0)))
            self.train_step = gspmd.make_gspmd_train_step(
                self.model, self.optimizer, self.mesh, loss_name=train_loss,
                example_batch=example, accum_steps=cfg.accum_steps,
                with_metrics=self.telemetry_metrics,
                update_sharding=cfg.update_sharding)
            self.eval_step = gspmd.make_gspmd_eval_step(
                self.model, self.mesh, loss_name=cfg.loss,
                with_accuracy=(cfg.loss == "cross_entropy"),
                example_batch=example)
        else:
            # the replicated update on a TPU data mesh of more than one
            # chip: each matrix's all-reduce beside the backward pass
            # (nothing elsewhere).  The options go to the outermost jit:
            # the step's own, or the scan's under --steps_per_dispatch k
            if cfg.update_sharding == "replicated":
                exchange_options = dp.exchange_overlap_options(self.mesh)
            self.train_step = dp.make_train_step(
                self.model, self.optimizer, self.mesh, loss_name=train_loss,
                grad_reduction=cfg.grad_reduction,
                accum_steps=cfg.accum_steps,
                update_sharding=cfg.update_sharding,
                grad_clip=cfg.grad_clip if step_clips else 0.0,
                with_metrics=self.telemetry_metrics,
                update_plan=self.update_plan,
                compiler_options=(exchange_options
                                  if int(cfg.steps_per_dispatch) <= 1
                                  else None))
            self.eval_step = dp.make_eval_step(
                self.model, self.mesh, loss_name=cfg.loss,
                with_accuracy=(cfg.loss == "cross_entropy"))
        # fault schedule parsed once (utils.faults; fit reuses it so
        # max=/once= counters survive across epochs).  The deterministic
        # desync (desync@N?det) is consumed HERE, at step-build time: it
        # wraps the jitted step so one replica drifts inside the program
        # itself — the software-bug stand-in the SDC replay triage must
        # prove deterministic (DESIGN.md §9)
        from ..utils.faults import FaultPlan

        self.fault_plan = FaultPlan.from_config(cfg.faults)
        det = self.fault_plan.det_desync() if self.fault_plan else None
        if det is not None:
            if (self.pipeline or self.expert or self.sp_tp or self.ep_tp
                    or self.gspmd or self.zero1 or self.sharded):
                raise NotImplementedError(
                    "desync?det perturbs the fully-replicated train state "
                    "inside the step; it is wired on the plain DP and "
                    "DP x seq layouts (replicated update)")
            from ..utils.faults import wrap_step_with_desync

            self.train_step = wrap_step_with_desync(
                self.train_step, self.mesh, det.start, det.eps)
        # compile-event ledger seam (utils/compile_ledger, DESIGN.md §7):
        # every layout's train/eval program goes through ONE
        # instrumentation point — while a ledger is installed
        # (--trace/--trace_dir) each new arg-shape/dtype signature is
        # compiled exactly once with wall time, HLO fingerprint, cost
        # analysis and recompile attribution recorded; with no ledger
        # the wrappers are pass-throughs.  The expert/ep_tp branches
        # instrumented their inner jitted program above.
        self.layout_tag = ("pipe" if self.pipeline else
                           "ep_tp" if self.ep_tp else
                           "expert" if self.expert else
                           "sp_tp" if self.sp_tp else
                           "sp" if self.seq_parallel else
                           "gspmd" if self.gspmd else "dp")
        if cfg.update_sharding != "replicated":
            self.layout_tag += f"+{cfg.update_sharding}"
        if cfg.model.matmul_dtype != "bf16":
            # the ledger names each (layout, matmul_dtype) pair's program:
            # a format change is a NEW named compile event; flipping the
            # calibration state (amax values, shapes fixed) is not
            self.layout_tag += f"+matmul_dtype={cfg.model.matmul_dtype}"
        if not (self.expert or self.ep_tp):
            self.train_step = ledger_lib.instrument(
                self.train_step, f"train_step[{self.layout_tag}]")
        self.eval_step = ledger_lib.instrument(
            self.eval_step, f"eval_step[{self.layout_tag}]")
        # silent-data-corruption defense (utils.consistency, DESIGN.md
        # §9): --sdc_check_every fingerprints the replicated state at
        # this cadence and heals transient divergence; the legacy
        # --check_replicas_every rides the same fingerprint path (same
        # lag-2 fetch discipline — the old host-side full-state fetch
        # stalled the async pipeline exactly the way DESIGN §7 warns
        # against) but stays detect-only: no healing, a divergence
        # localizes, triages and raises.
        self.sdc_every = (int(cfg.sdc_check_every)
                          or int(cfg.check_replicas_every))
        self.sdc_heal = bool(cfg.sdc_heal) and int(cfg.sdc_check_every) > 0
        self._fp = None           # consistency.Fingerprinter, built in fit
        self._sdc_policy = None   # resilience.SDCPolicy
        self._sdc_batch = None    # last dispatched batch, for replay triage
        # multi-step dispatch (--steps_per_dispatch k, VERDICT r4 item 6):
        # one jitted lax.scan runs k optimizer steps over a device-staged
        # batch stack, amortizing the per-step host dispatch that dominates
        # small models (the reference pays a gather-average-send round trip
        # EVERY step, :149-211; MNIST MLP measured dispatch-bound at 0.011
        # MFU).  The scan replays the identical batches in the identical
        # order: bitwise-identical to k=1 on the plain-DP shard_map path,
        # same-math-within-compile-noise on the scanned GSPMD/SP bodies
        # (tests/test_dispatch.py bounds the drift).
        self.k_dispatch = max(1, int(cfg.steps_per_dispatch))
        if self.k_dispatch > 1:
            from jax import lax

            inner = self.train_step

            def multi(state, stacked):
                return lax.scan(lambda s, b: inner(s, b), state, stacked)

            # donate the carried state: the caller always discards the old
            # one, and k>1 exists to cut overhead, not add copies
            self.multi_step = ledger_lib.instrument(
                jax.jit(multi, donate_argnums=0,
                        compiler_options=exchange_options or None),
                f"multi_step[{self.layout_tag},k={self.k_dispatch}]")
        # distributed tracing (train/trace.py): install the span tracer
        # + compile ledger for this process.  Validates the flag combo
        # (--trace needs --telemetry_dir or --trace_dir) eagerly.
        self.tracer = None
        trace_dir = trace_lib.dir_from_config(cfg)
        if trace_dir:
            self.tracer = trace_lib.start_run(trace_dir)
        self.metrics = MetricsLogger(cfg.metrics_jsonl)
        dev = self.mesh.devices.flat[0]
        self.telemetry = telemetry_lib.Telemetry(
            cfg, self.model, tuple(self.data["x"].shape[1:]),
            n_devices=int(self.mesh.devices.size),
            device_kind=dev.device_kind, platform=dev.platform)
        self.state: Optional[TrainState] = None

    # ---- state lifecycle -------------------------------------------------
    def init_state(self) -> TrainState:
        """Deterministic init — every host derives identical params from the
        job seed (replaces the reference's rank-0 state-dict bcast, :87-88);
        placement is replicated for DP/SP or TP/FSDP-sharded for GSPMD."""
        if self.pipeline:
            from ..parallel import pipeline as pp

            state = pp.init_pipeline_state(
                self.model, self.optimizer, prng.init_key(self.cfg.seed),
                int(self.mesh.shape["pipe"]),
                tp=int(self.mesh.shape.get("tensor", 1)),
                interleave=self.cfg.pp_interleave)
            self.state = pp.shard_pipeline_state(
                state, self.mesh, self.optimizer,
                interleave=self.cfg.pp_interleave)
            return self.state
        from ..ops import qmm

        if self.zero1:
            import jax.numpy as jnp

            params = self.model.init(prng.init_key(self.cfg.seed))
            host = TrainState(
                step=jnp.zeros((), jnp.int32), params=params,
                opt_state=dp.zero1_opt_state(self.optimizer, params,
                                             self.mesh, place=False),
                qstate=qmm.init_qstate(self.model))
            self.state = dp.place_zero1_state(host, self.mesh,
                                              self.optimizer)
            return self.state
        if self.sharded and not self.gspmd:
            import jax.numpy as jnp

            from ..parallel import update_sharding as us_lib

            params = self.model.init(prng.init_key(self.cfg.seed))
            host = TrainState(
                step=jnp.zeros((), jnp.int32), params=params,
                opt_state=us_lib.init_opt_state(self.optimizer, params,
                                                self.update_plan),
                qstate=qmm.init_qstate(self.model))
            self.state = us_lib.place_state(host, self.mesh,
                                            self.optimizer,
                                            self.update_plan)
            return self.state
        if self.sp_tp:
            from ..parallel import spmd

            state = spmd.init_sp_tp_state(
                self.model, self.optimizer, prng.init_key(self.cfg.seed),
                int(self.mesh.shape["tensor"]))
            self.state = spmd.shard_sp_tp_state(
                state, self.mesh, self.optimizer,
                vocab_parallel=self.cfg.vocab_parallel)
            return self.state
        if self.ep_tp:
            from ..parallel import expert as ep_lib

            state = ep_lib.init_moe_tp_state(
                self.model, self.optimizer, prng.init_key(self.cfg.seed),
                int(self.mesh.shape["tensor"]))
            self.state = ep_lib.shard_moe_tp_state(state, self.mesh,
                                                   self.optimizer)
            return self.state
        state = TrainState.create(self.model, self.optimizer,
                                  prng.init_key(self.cfg.seed))
        if self.expert:
            from ..parallel import expert as ep_lib

            self.state = ep_lib.shard_moe_state(state, self.mesh,
                                                self.optimizer)
        elif self.gspmd:
            from ..parallel import gspmd

            self.state = gspmd.shard_state(
                self.model, state, self.optimizer, self.mesh,
                update_sharding=self.cfg.update_sharding)
        else:
            self.state = dp.replicate_state(state, self.mesh)
        return self.state

    def _elastic_preflight(self, cfg: TrainConfig) -> TrainConfig:
        """Detect a cross-world elastic resume BEFORE the loader and step
        builders exist, and apply the ``--elastic_batch`` policy
        (DESIGN.md §10).  Keyed to the newest VERIFIED generation — the
        one restore() will actually land on — not merely the newest
        committed one: a corrupt newest generation saved by a
        different-sized world (say a degraded dp=2 save above healthy
        dp=8 history) would otherwise derive the policy from metadata of
        a snapshot that restore quarantines and falls back past.  The
        extra checksum pass happens once per process start, on the same
        chain restore re-verifies moments later."""
        if not (cfg.elastic and cfg.resume and cfg.checkpoint_dir):
            return cfg
        import dataclasses
        import math

        from ..utils import checkpoint as ckpt

        step = ckpt.newest_verified_step(cfg.checkpoint_dir)
        meta = (ckpt.read_meta(cfg.checkpoint_dir, step=step)
                if step is not None else None) or {}
        saved = meta.get("saved_world") or {}
        saved_dp = int(saved.get("dp") or 0)
        new_dp = int(np.prod([self.mesh.shape[a]
                              for a in self.batch_axes]))
        if not saved_dp or saved_dp == new_dp:
            return cfg
        change = {
            "from_world": saved,
            "to_world": {"n_devices": jax.device_count(),
                         "n_processes": jax.process_count(),
                         "dp": new_dp},
            "policy": cfg.elastic_batch,
            "batch_size": [cfg.batch_size, cfg.batch_size],
            "accum_steps": [cfg.accum_steps, cfg.accum_steps],
        }
        if cfg.elastic_batch == "per_device" and not cfg.full_batch:
            # keep per-device rows: shrink/grow the global batch with the
            # world; round to a multiple of the new dp so padding stays
            # padding, never a silent second batch-size change
            new_bs = max(new_dp,
                         (round(cfg.batch_size * new_dp / saved_dp)
                          // new_dp) * new_dp or new_dp)
            change["batch_size"][1] = new_bs
            cfg = dataclasses.replace(cfg, batch_size=new_bs)
        elif cfg.elastic_batch == "global" and saved_dp > new_dp:
            # keep the global batch: per-device rows grow by
            # saved_dp/new_dp — raise grad accumulation by the same
            # factor to bound per-device microbatch memory, but only
            # when the per-shard rows stay divisible (accumulation
            # reshapes the local shard into microbatches)
            factor = math.ceil(saved_dp / new_dp)
            new_accum = cfg.accum_steps * factor
            bs = (self.data["x"].shape[0] if cfg.full_batch
                  else cfg.batch_size)
            per_shard = math.ceil(bs / new_dp)
            if per_shard % new_accum == 0:
                change["accum_steps"][1] = new_accum
                cfg = dataclasses.replace(cfg, accum_steps=new_accum)
        self._topology_change = change
        log(f"[elastic] resuming a dp={saved_dp} checkpoint on dp="
            f"{new_dp} ({saved.get('n_devices', '?')} -> "
            f"{jax.device_count()} devices), policy="
            f"{cfg.elastic_batch}: batch {change['batch_size'][0]} -> "
            f"{change['batch_size'][1]}, accum "
            f"{change['accum_steps'][0]} -> {change['accum_steps'][1]}")
        return cfg

    def maybe_resume(self) -> int:
        """Restores state and returns the exact global step to resume from
        (checkpoint extension).  Mid-epoch checkpoints resume at the right
        batch within the epoch — no step is replayed.  Elastic resumes
        onto a different world ride the reshard path (utils.checkpoint)
        and, when the batch size changed with the world, re-derive the
        (epoch, in-epoch step) start from the world-size-independent
        ``consumed_samples`` meta so the sample stream stays a permutation
        of the original epoch."""
        if not (self.cfg.resume and self.cfg.checkpoint_dir):
            return 0
        from ..utils import checkpoint as ckpt

        restored = ckpt.restore(self.cfg.checkpoint_dir, self.state,
                                elastic=self.cfg.elastic)
        if restored is None:
            return 0
        restored = self._reconcile_qkv_tp(ckpt, restored)
        self._place_restored(restored)
        # restore the anomaly-rollback order salt: a relaunch after a
        # rollback must keep the re-drawn data order, not replay the
        # poison window and re-spend the rollback budget on it.  Read the
        # meta of the generation restore ACTUALLY loaded (its step) — the
        # newest committed dir can be a different, corrupt generation when
        # quarantine failed (read-only fs) or this is a non-leader process
        meta = ckpt.read_meta(self.cfg.checkpoint_dir,
                              step=int(jax.device_get(self.state.step))) or {}
        self.loader.order_salt = int(meta.get("order_salt", 0))
        if self.cfg.elastic:
            # topology lineage: a shrunken world's own saves must carry
            # the ORIGINAL topology forward, not shadow it — propagate
            # the oldest restored_world on record, else the saving world
            self._restored_world = (meta.get("restored_world")
                                    or meta.get("saved_world"))
        start_step = int(jax.device_get(self.state.step))
        self._remap_step_offset(meta, start_step)
        return start_step

    def _remap_step_offset(self, meta: dict, start_step: int) -> None:
        """After a batch-size-changing elastic resume, map the restored
        generation's step counter onto THIS loader's (epoch, in-epoch)
        position via the world-size-independent ``consumed_samples``
        meta.  Keyed to the generation actually restored — an anomaly
        rollback that falls back to an older (possibly old-world)
        snapshot must recompute the offset for THAT step, not keep the
        one derived for the generation the run originally resumed."""
        self._step_offset = 0
        self._resume_plan = None
        if (self._topology_change is None
                or self._topology_change["batch_size"][0]
                == self._topology_change["batch_size"][1]
                or meta.get("consumed_samples") is None):
            return
        plan = self.loader.start_for_samples(
            int(meta["consumed_samples"]))
        spe = max(self.loader.steps_per_epoch, 1)
        self._resume_plan = plan
        self._step_offset = plan[0] * spe + plan[1] - start_step
        log(f"[elastic] batch size changed with the world: resuming "
            f"at epoch {plan[0]}, in-epoch step {plan[1]} from "
            f"consumed_samples={meta['consumed_samples']}")

    def _place_restored(self, restored: TrainState) -> None:
        """Place a host-side restored state per this trainer's layout
        (shared by resume and anomaly rollback)."""
        if self.pipeline:
            from ..parallel import pipeline as pp

            self.state = pp.shard_pipeline_state(
                restored, self.mesh, self.optimizer,
                interleave=self.cfg.pp_interleave)
        elif self.sp_tp:
            from ..parallel import spmd

            self.state = spmd.shard_sp_tp_state(
                restored, self.mesh, self.optimizer,
                vocab_parallel=self.cfg.vocab_parallel)
        elif self.ep_tp:
            from ..parallel import expert as ep_lib

            self.state = ep_lib.shard_moe_tp_state(restored, self.mesh,
                                                   self.optimizer)
        elif self.expert:
            from ..parallel import expert as ep_lib

            self.state = ep_lib.shard_moe_state(restored, self.mesh,
                                                self.optimizer)
        elif self.gspmd:
            from ..parallel import gspmd

            self.state = gspmd.shard_state(
                self.model, restored, self.optimizer, self.mesh,
                update_sharding=self.cfg.update_sharding)
        elif self.zero1:
            self.state = dp.place_zero1_state(restored, self.mesh,
                                              self.optimizer)
        elif self.sharded:
            from ..parallel import update_sharding as us_lib

            self.state = us_lib.place_state(restored, self.mesh,
                                            self.optimizer,
                                            self.update_plan)
        else:
            self.state = dp.replicate_state(restored, self.mesh)

    def _rollback(self) -> int:
        """Anomaly rollback (train.resilience): restore the newest
        checkpoint (or the deterministic init when none exists yet) and
        re-draw the subsequent data order so the poison window is not
        replayed verbatim.  Returns the global step to resume from."""
        from ..utils import checkpoint as ckpt

        restored = None
        if self.cfg.checkpoint_dir:
            ckpt.wait_pending()  # an in-flight async write may be newest
            # elastic rides along: right after a degraded relaunch the
            # newest verified snapshot can still be the OLD world's
            restored = ckpt.restore(self.cfg.checkpoint_dir, self.state,
                                    elastic=self.cfg.elastic)
        if restored is None:
            self.init_state()  # no snapshot yet: back to step 0
            self._step_offset = 0
            self._resume_plan = None
        else:
            restored = self._reconcile_qkv_tp(ckpt, restored)
            self._place_restored(restored)
            step = int(jax.device_get(self.state.step))
            # the fallback chain may land on an OLDER generation than
            # the one the elastic resume was keyed to: re-derive the
            # step->position offset from that generation's meta
            self._remap_step_offset(
                ckpt.read_meta(self.cfg.checkpoint_dir, step=step) or {},
                step)
        self.loader.order_salt += 1
        # the retrained window will revisit step numbers already saved —
        # with DIFFERENT state (re-drawn order); the final-save skip
        # must never treat those as already-committed
        self._last_saved_step = None
        return int(jax.device_get(self.state.step))

    # ---- silent-data-corruption defense (DESIGN.md §9) -------------------
    def _sdc_observe(self, at_step: int, fp, watchdog,
                     draining: bool = False) -> str:
        """Consume one lag-2 fingerprint: fetch the tiny per-device digest
        vector, form the GLOBAL verdict (in a multi-host world the digests
        are allgathered, so every process computes the identical verdict
        and takes the same branch — the incident path contains
        collectives), and on mismatch run the incident pipeline.  Returns
        ``"ok"``, ``"healed"`` or ``"rollback"``."""
        from ..parallel import distributed
        from ..utils import consistency

        digests, folds = consistency.Fingerprinter.fetch(fp)
        if distributed.is_multi_host():
            mat = np.asarray(distributed.allgather_host_array(digests))
        else:
            mat = digests[None, :]
        verdict = consistency.digest_report(mat)
        if not verdict:
            return "ok"
        return self._sdc_incident(at_step, verdict, folds, watchdog,
                                  draining)

    def _sdc_incident(self, at_step: int, fp_verdict: dict, folds,
                      watchdog, draining: bool) -> str:
        """Fingerprint mismatch: localize → record → replay-triage → heal
        or abort.  ``fp_verdict`` is identical on every process (computed
        from gathered digests), so every branch that reaches a collective
        is taken by all processes together; only the purely-local heal
        (device_put of the majority shard) differs per host."""
        from ..parallel import distributed
        from ..utils import consistency
        from .resilience import SDCAbort

        cfg = self.cfg
        log(f"[sdc] fingerprint mismatch detected for step {at_step} "
            f"(checked at lag 2): localizing...")
        with watchdog.suspended():
            # ---- localize: which leaf, which shard, which device -------
            report = consistency.divergence_report(self.state)
            cross = {}
            if fp_verdict.get("cross"):
                # cross-host sweep: each host's shard-0 content digest
                # per leaf, gathered and compared (collective; symmetric
                # because fp_verdict is)
                cross = distributed.cross_host_report(
                    consistency.leaf_digests(self.state))
            devices = sorted({d for r in report.values()
                              for d in r["devices"]})
            # ---- replay triage: deterministic bug vs transient fault ---
            # re-execute the last dispatch from a consistency-restored
            # state (majority-shard heal of the pre-replay snapshot) and
            # fingerprint the result: a software bug (lying out_spec,
            # miscompiled collective, desync?det) re-diverges every time;
            # a cosmic ray does not.  The replay input is a COPY — the
            # step donates its argument, and the healed state must
            # survive to continue training.
            healed, _ = consistency.heal_replication(self.state, report)
            replay_verdict = "unknown"
            if self._sdc_batch is not None and self._fp is not None:
                import jax.numpy as jnp

                replay_in = jax.tree_util.tree_map(jnp.copy, healed)
                step_fn = (self.multi_step if self.k_dispatch > 1
                           else self.train_step)
                replay_out, _ = step_fn(replay_in, self._sdc_batch)
                r_digests, _rf = consistency.Fingerprinter.fetch(
                    self._fp.compute(replay_out))
                if distributed.is_multi_host():
                    r_mat = np.asarray(
                        distributed.allgather_host_array(r_digests))
                else:
                    r_mat = r_digests[None, :]
                replay_verdict = ("deterministic"
                                  if consistency.digest_report(r_mat)
                                  else "transient")
            # ---- decide + record --------------------------------------
            cross_procs = list(fp_verdict.get("cross", []))
            strike_keys = devices + [f"process:{p}" for p in cross_procs]
            record = {
                "step": int(at_step),
                "leaves": {k: {"shards": r["shards"],
                               "devices": r["devices"],
                               "max_abs_diff": float(r["max_abs_diff"]),
                               "n_bad_elements": int(r["n_bad_elements"])}
                           for k, r in report.items()},
                "devices": devices,
                "cross_host": {k: v["processes"] for k, v in cross.items()}
                              if cross else {},
                "float_folds": [float(f) for f in folds],
                "verdict": replay_verdict,
            }
            if replay_verdict == "deterministic":
                record["action"] = "abort_deterministic"
                self.telemetry.on_sdc(record)
                names = (sorted(report) or sorted(cross)
                         or ["<unlocalized>"])
                raise SDCAbort(
                    f"replica divergence at step {at_step} REPRODUCED on "
                    f"replay from a consistency-restored state — "
                    f"deterministic software bug in the step function "
                    f"(diverged leaves: {names[:5]}); a relaunch would "
                    "replay it.  Suspects: a shard_map out_spec claiming "
                    "replication the math does not guarantee (check_vma "
                    "off), a nondeterministic kernel, or an injected "
                    "desync?det")
            exhausted = self._sdc_policy.record(strike_keys)
            if exhausted:
                record["action"] = "abort_strikes"
                record["strikes"] = dict(self._sdc_policy.counts)
                self.telemetry.on_sdc(record)
                raise SDCAbort(
                    f"transient replica divergence at step {at_step}, but "
                    f"{exhausted} exceeded the strike budget "
                    f"(--sdc_strikes {cfg.sdc_strikes}; counts "
                    f"{self._sdc_policy.counts}) — repeatedly flaky "
                    "hardware; drain the device instead of relaunching")
            if not self.sdc_heal:
                record["action"] = "detect_only"
                self.telemetry.on_sdc(record)
                worst = sorted(((k, r["max_abs_diff"])
                                for k, r in report.items()),
                               key=lambda kv: -kv[1])[:5]
                raise AssertionError(
                    f"replica divergence in train state @ step {at_step}: "
                    f"{len(report)} replicated leaves differ across device "
                    f"shards (worst: {worst}; cross-host: "
                    f"{record['cross_host']}); replay says "
                    f"{replay_verdict}.  Healing is off on this path — "
                    "use --sdc_check_every/--sdc_heal to heal instead of "
                    "dying")
            if cross_procs or (cross and not report):
                # hosts disagree while each host is internally consistent:
                # a local majority vote cannot pick the truth — roll back
                # to the newest VERIFIED checkpoint (identical bytes on
                # every host, DESIGN.md §8 machinery)
                record["action"] = "rollback"
                self.telemetry.on_sdc(record)
                if draining:
                    # transient + recoverable, so NOT SDCAbort/45 (the
                    # supervisor would refuse to relaunch a perfectly
                    # retryable job): die as a plain crash — the relaunch
                    # resumes from the newest verified checkpoint, which
                    # is exactly the mid-run rollback action anyway
                    raise RuntimeError(
                        f"[sdc] cross-host divergence detected at step "
                        f"{at_step} during the final drain — refusing to "
                        "write a final snapshot from unreconcilable "
                        "state; relaunch/resume from the newest verified "
                        "checkpoint")
                return "rollback"
            # transient, local, under budget: HEAL — restore replication
            # from the majority shard and keep training
            record["action"] = "healed"
            record["strikes"] = dict(self._sdc_policy.counts)
            self.telemetry.on_sdc(record)
            if report:
                self.state = healed
                self._sdc_policy.healed += 1
                log(f"[sdc] transient divergence healed at step {at_step}: "
                    f"{len(report)} leaf/leaves restored from the majority "
                    f"shard (implicated: {devices}; strikes "
                    f"{self._sdc_policy.counts})")
            else:
                # a PEER host healed its local divergence this round; this
                # host had nothing to repair
                log(f"[sdc] divergence at step {at_step} localized to a "
                    "peer host's shards; no local repair needed")
            return "healed"

    def _reconcile_qkv_tp(self, ckpt, restored: TrainState) -> TrainState:
        """The TP qkv column permutation is shape-preserving, so a
        checkpoint written under a different tensor-axis size is
        undetectable from the pytree alone — meta.json records it
        (checkpoint.save extra_meta) and we re-permute here, for params
        AND every optimizer slot (momentum/mu/nu mirror the param layout
        and carry the same permutation).  Runs on EVERY resume path: only
        the explicit shard_map TP layouts (pipeline, seq x tensor) use the
        permutation — plain DP/SP/GSPMD trainers expect the dense column
        order, so a checkpoint from a permuted layout must be unpermuted
        even when this trainer has no tensor axis at all.  Missing metadata
        means a dense-layout save (every save records qkv_tp since round 2;
        only the explicit-TP layouts ever set it > 1), so the default is 1
        — NOT the current tp, which would silently treat a dense checkpoint
        as already permuted when resuming INTO a TP layout."""
        tp = (int(self.mesh.shape.get("tensor", 1))
              if (self.pipeline or self.sp_tp or self.ep_tp) else 1)
        # meta of the generation actually restored, not the newest on disk
        # (they differ when the fallback chain skipped an unquarantinable
        # corrupt generation) — a mismatched qkv_tp would silently
        # mis-permute the qkv columns of an older generation's weights
        meta = ckpt.read_meta(self.cfg.checkpoint_dir,
                              step=int(np.asarray(restored.step))) or {}
        saved_tp = int(meta.get("qkv_tp", 1))
        if saved_tp == tp:
            return restored
        if not (isinstance(restored.params, dict)
                and "blocks" in restored.params):
            return restored  # non-transformer state carries no permutation
        from ..parallel import megatron

        c = self.model.cfg

        def fix(tree):
            if not (isinstance(tree, dict) and "blocks" in tree):
                return tree  # e.g. the optimizer's step counter
            tree = dict(tree)
            b = tree["blocks"]
            if saved_tp > 1:
                b = megatron.permute_qkv(b, c.d_model, c.n_heads,
                                         saved_tp, inverse=True,
                                         kv_heads=c.kv_heads)
            if tp > 1:
                b = megatron.permute_qkv(b, c.d_model, c.n_heads, tp,
                                         kv_heads=c.kv_heads)
            tree["blocks"] = b
            return tree

        def fix_state(st):
            # recurse through NamedTuple slots (SGDState/AdamState, and
            # the guard wrapper's GuardedState around them) down to the
            # param-mirroring dicts fix() permutes
            if isinstance(st, tuple) and type(st) is not tuple:
                return type(st)(*(fix_state(f) for f in st))
            return fix(st)

        # qstate passes through untouched: the fp8 calibration histories
        # carry no qkv column layout, and dropping them here would
        # silently reset delayed scaling on any resume that re-permutes
        return TrainState(step=restored.step, params=fix(restored.params),
                          opt_state=fix_state(restored.opt_state),
                          qstate=restored.qstate)

    def save(self, final: bool = False) -> None:
        # every process calls in: checkpoint.save is leader-only for
        # addressable state and shard-parallel (orbax) for TP/FSDP state
        # that spans hosts (device_get would raise there)
        if self.cfg.checkpoint_dir:
            from ..utils import checkpoint as ckpt

            # checkpoint writes emit no dispatches; keep the external
            # stale-heartbeat monitor from reading a long write as a hang
            self.telemetry.alive()
            # record the (shape-preserving, hence otherwise undetectable)
            # TP qkv permutation so maybe_resume can reconcile a different
            # tensor-axis size; dense layouts record 1 explicitly.  The
            # rollback salt rides along so a supervised relaunch resumes
            # with the re-drawn data order instead of replaying a poison
            # window the in-process rollback already routed around.
            # saved_world enriches checkpoint.current_world with the
            # layout facts only the trainer knows (dp width, mesh shape,
            # update sharding — what the cross-world reshard path keys
            # off); restored_world carries the ORIGINAL topology lineage
            # so a shrunken world's saves never shadow where the run
            # started; consumed_samples is the world-size-independent
            # progress coordinate an elastic resume with a different
            # batch size maps through (DESIGN.md §10).
            step_now = int(jax.device_get(self.state.step))
            # when the run ENDS exactly on a checkpoint boundary, the
            # loop's periodic save already committed this step and the
            # state has not changed since — the final save would rewrite
            # the same generation, which the orbax (multi-process) layout
            # refuses ("Destination already exists") and the npz layout
            # pays as a redundant full write.  Drain the async writer and
            # return: the committed generation IS the final snapshot.
            if final and self._last_saved_step == step_now:
                ckpt.wait_pending()
                return
            self._last_saved_step = step_now
            extra = {"qkv_tp": (int(self.mesh.shape.get("tensor", 1))
                                if (self.pipeline or self.sp_tp
                                    or self.ep_tp) else 1),
                     "order_salt": int(getattr(self.loader,
                                               "order_salt", 0)),
                     "saved_world": {
                         "dp": int(self.loader.dp),
                         "mesh": {k: int(v)
                                  for k, v in self.mesh.shape.items()},
                         "update_sharding": self.cfg.update_sharding},
                     "consumed_samples":
                         self.loader.consumed_samples(
                             step_now + self._step_offset)}
            if self._restored_world:
                extra["restored_world"] = self._restored_world
            # span "ckpt" = this call's host-side cost (the async path's
            # staging device_get); the writer thread's disk time shows
            # separately as "ckpt_write" (utils/checkpoint)
            with trace_lib.span("ckpt", step=step_now, final=final):
                if self.cfg.async_checkpoint and not final:
                    ckpt.save_async(self.cfg.checkpoint_dir, self.state,
                                    keep=self.cfg.checkpoint_keep,
                                    extra_meta=extra)
                else:
                    if final:  # drain in-flight writes before the last
                        ckpt.wait_pending()
                    ckpt.save(self.cfg.checkpoint_dir, self.state,
                              keep=self.cfg.checkpoint_keep,
                              extra_meta=extra)

    # ---- the loop --------------------------------------------------------
    def fit(self) -> Dict[str, Any]:
        cfg = self.cfg
        if self.state is None:
            self.init_state()
        spe = max(self.loader.steps_per_epoch, 1)
        start_step = self.maybe_resume()
        # _step_offset is 0 except after an elastic resume whose batch
        # size changed with the world — there the continuing step counter
        # maps onto a different (epoch, in-epoch) position
        start_epoch = (start_step + self._step_offset) // spe
        if self._topology_change is not None:
            self.telemetry.on_topology(
                int(start_step), dict(self._topology_change))
        update_note = ""
        if cfg.update_sharding != "replicated":
            update_note = (f" | update: {cfg.update_sharding}"
                           + (" + master weights" if cfg.master_weights
                              else "")
                           + (f" ({cfg.model.dtype} params)"
                              if cfg.model.dtype != "float32" else ""))
        log(f"mesh: {describe(self.mesh)} | model: {cfg.model.arch} "
            f"({self.model.n_params():,} params) | "
            f"{self.loader.n} samples, "
            f"{self.loader.steps_per_epoch} steps/epoch{update_note}")
        # --xla_trace_dir: the leader-gated jax.profiler DEVICE capture
        # (utils.profiling.trace) next to the host spans — same knob as
        # the legacy --profile_dir
        profiler = profiling.trace(cfg.profile_dir or cfg.xla_trace_dir)
        thr = Throughput()
        timer = profiling.StepTimer()
        last_loss = float("nan")
        # host-side step counter: the loop never reads the step from the
        # device.  Loss logging lags one step, but as the loop is written
        # the fetch of step k-1's loss comes BEFORE the dispatch of step
        # k: where it is due (every step at log_every=1) the device_get
        # drains the device, which then sits idle for the whole of the
        # `dispatch` span that follows.  Dispatching first would hide
        # that span behind step k-1 (ROADMAP S5).
        step = start_step
        prev: Optional[tuple] = None  # (step, epoch, loss_future)
        last_eval: Optional[tuple] = None  # (step, metrics dict)
        # hang watchdog (SURVEY.md §5.3): with log_every on, the loop blocks
        # in device_get on the previous step's loss, so a stalled device
        # stalls the pats and the watchdog fires instead of hanging forever
        from ..utils.watchdog import HangWatchdog
        from .resilience import (AnomalyAbort, GracefulShutdown,
                                 ResilienceMonitor, SDCPolicy)

        # the watchdog's last act before exit 42 is a flight-recorder
        # dump: the postmortem then says what the run was doing when the
        # device wedged (telemetry.emergency_dump is a no-op when off)
        watchdog = HangWatchdog(
            cfg.hang_timeout or None,
            on_timeout=lambda: telemetry_lib.emergency_dump("hang"))
        # one lap a dispatch, marked ahead of the loader's ``next()``: a
        # step that ran long is recorded with where it stood
        # (train/trace.py "Laps and stalls")
        laps = self.telemetry.laps = trace_lib.LapWatch("train_step")
        # anomaly policy (DESIGN.md §6): consumes the per-step loss
        # futures at a fixed lag of two dispatches, so its device_get only
        # ever waits on a step whose successor is already submitted — one
        # dispatch stays in flight and the async pipeline keeps host prep
        # overlapped with device compute (the pure lag-1 logging path
        # semantics are unchanged when the monitor is off)
        monitor = (ResilienceMonitor(cfg.rollback_after, cfg.max_rollbacks,
                                     cfg.loss_spike_factor)
                   if cfg.rollback_after > 0 else None)
        monitor_q: list = []  # (step, loss future), observed at lag 2
        fault_plan = self.fault_plan
        # SDC fingerprint monitor (DESIGN.md §9): one jitted O(1) digest
        # per check, queued and fetched at the same lag-2 discipline as
        # the loss monitor — routine checking never drains the pipeline
        sdc_q: list = []  # (step, fingerprint futures), observed at lag 2
        if self.sdc_every:
            from ..parallel import distributed
            from ..utils import consistency

            fpr = consistency.Fingerprinter(self.state, self.mesh)
            if fpr.n_leaves and (fpr.n_local_shards > 1
                                 or distributed.is_multi_host()):
                self._fp = fpr
                self._sdc_policy = SDCPolicy(cfg.sdc_strikes)
            else:
                self._fp = None
                log("[sdc] replica checking disabled: no replicated "
                    "leaves with >= 2 device shards in this layout/mesh")
        # preemption-safe exit: SIGTERM/SIGINT set a flag checked at each
        # dispatch boundary -> final checkpoint -> exit 0 (<= 1 lost step)
        shutdown = GracefulShutdown()
        dispatches = None

        def do_rollback(why: str) -> None:
            """Shared rollback bookkeeping (anomaly monitor + SDC
            cross-host heal): restore the newest verified snapshot,
            re-draw the data order, dump/rearm the postmortem, and reset
            BOTH lag queues — their futures belong to the abandoned
            timeline.  The caller breaks out of the dispatch loop."""
            nonlocal step, prev, rolled_back
            with trace_lib.span("rollback"), watchdog.suspended():
                step = self._rollback()
            log(f"{why} — restored step {step}, re-drew the data order")
            # postmortem now + a straddling re-dump after the first
            # post-rollback record
            self.telemetry.on_rollback(step,
                                       monitor.rollbacks if monitor else 0)
            prev = None
            monitor_q.clear()
            sdc_q.clear()
            rolled_back = True

        def sdc_pump(keep: int, draining: bool = False) -> str:
            """Observe queued SDC fingerprints down to ``keep`` entries.
            ``keep=1`` is the routine lag-2 discipline (one dispatch
            stays in flight); ``keep=0`` drains — used right before a
            snapshot and at the end of the run, so state the fingerprint
            has not yet cleared can never be captured to disk unobserved.
            Returns "ok", "healed" (queue cleared: pre-heal fingerprints
            are stale) or "rollback" (the caller rolls back)."""
            while len(sdc_q) > keep:
                act = self._sdc_observe(*sdc_q.pop(0), watchdog=watchdog,
                                        draining=draining)
                if act == "healed":
                    sdc_q.clear()
                    return "healed"
                if act == "rollback":
                    return "rollback"
            return "ok"

        try:
            with profiler, watchdog, shutdown:
                epoch = start_epoch
                # in-epoch offset, consumed by the first epoch iteration only
                # (and re-seeded by a rollback); mirrors the old
                # `epoch == start_epoch` special case
                mid_epoch_start = (start_step + self._step_offset) % spe
                while epoch < cfg.nepochs and not shutdown.requested:
                    log(f"Starting epoch {epoch + 1}")  # reference banner, :152
                    epoch_t0 = time.perf_counter()
                    epoch_start_step = mid_epoch_start
                    mid_epoch_start = 0
                    loss = None
                    rolled_back = False
                    if self.k_dispatch > 1:
                        # (stacked k-batch, n_steps, rows) per host dispatch;
                        # loss logging reports each dispatch's LAST step (the
                        # intermediate losses live only inside the scan)
                        dispatches = self.loader.epoch_groups(
                            epoch, self.k_dispatch, start_step=epoch_start_step)
                    else:
                        dispatches = (
                            (b, 1, self.loader.batch_rows(epoch_start_step + i))
                            for i, b in enumerate(self.loader.epoch(
                                epoch, start_step=epoch_start_step)))
                    # each next() is a "load" span (host batch assembly)
                    dispatches = trace_lib.traced_iter(
                        "load", dispatches, before=lambda: laps.lap(step))
                    for batch, n_steps, rows in dispatches:
                        if shutdown.requested:
                            break
                        if monitor is not None and len(monitor_q) >= 2:
                            # observe at lag 2 (not the newest future): the
                            # device_get then waits only on a step that
                            # already has a successor submitted, so one
                            # dispatch stays in flight and the async
                            # pipeline keeps overlapping host batch prep
                            # with device compute even when log_every > 1
                            m_step, m_loss = monitor_q.pop(0)
                            with trace_lib.span("fetch", what="monitor",
                                                step=m_step):
                                m_val = float(jax.device_get(m_loss))
                            action = monitor.observe(m_val)
                            if action == "abort":
                                raise AnomalyAbort(
                                    f"training diverged at step {m_step}: "
                                    f"{monitor.bad_steps} bad steps and the "
                                    f"rollback budget (max_rollbacks="
                                    f"{cfg.max_rollbacks}) is exhausted")
                            if action == "rollback":
                                do_rollback(
                                    f"anomaly rollback "
                                    f"#{monitor.rollbacks}: "
                                    f"{cfg.rollback_after} consecutive "
                                    "bad steps")
                                break
                        # log when the dispatch CROSSED a log_every boundary
                        # (== the modulo rule at n_steps=1; prev[3] is the
                        # step count before that dispatch)
                        if prev is not None and cfg.log_every and \
                                prev[0] // cfg.log_every > prev[3] // cfg.log_every:
                            with trace_lib.span("fetch", what="log",
                                                step=prev[0]):
                                last_loss = float(jax.device_get(prev[2]))
                            self.metrics.write({
                                "step": prev[0], "epoch": prev[1],
                                "loss": last_loss,
                                "samples_per_sec": thr.samples_per_sec,
                            })
                        if fault_plan is not None:
                            # I/O fault kinds need the checkpoint dir
                            batch = fault_plan.apply(
                                step, batch, ckpt_dir=cfg.checkpoint_dir)
                            # SDC kinds (bitflip/desync) corrupt one
                            # replica shard of the device-placed state
                            self.state = fault_plan.apply_state(step,
                                                                self.state)
                        if self._fp is not None:
                            # retained for the replay triage (batches are
                            # not donated; holding one dispatch's worth
                            # of rows is the entire cost)
                            self._sdc_batch = batch
                        # "dispatch" measures the HOST-side submission
                        # cost (async — the device runs behind it)
                        with trace_lib.step_annotation(step), \
                                trace_lib.span("dispatch", step=step):
                            if self.k_dispatch > 1:
                                self.state, outs = self.multi_step(
                                    self.state, batch)
                                # each dispatch reports its LAST step
                                # (the intermediate outputs live inside
                                # the scan; the 'skipped' metric is the
                                # guard's CUMULATIVE counter exactly so
                                # this slice cannot lose mid-dispatch
                                # fires)
                                out = jax.tree_util.tree_map(
                                    lambda x: x[-1], outs)
                            else:
                                self.state, out = self.train_step(
                                    self.state, batch)
                        # telemetry layouts return the on-device metrics
                        # dict; everything downstream keys off the loss
                        loss = out["loss"] if isinstance(out, dict) else out
                        watchdog.pat()
                        timer.tick()  # one tick per DISPATCH (= n_steps steps)
                        thr.add(rows)
                        before = step
                        step += n_steps
                        prev = (step, epoch, loss, before)
                        if monitor is not None:
                            monitor_q.append((step, loss))
                        # lag-2 fetch + metrics record + heartbeat refresh
                        self.telemetry.on_dispatch(step, epoch, before, out,
                                                   n_steps, rows)
                        # k>1 dispatches can stride over an exact multiple;
                        # fire on every boundary CROSSING (== the k=1 modulo
                        # rule when n_steps is 1).  While the monitor's
                        # bad-step streak is nonzero the snapshot is SKIPPED
                        # (next boundary saves): checkpointing mid-anomaly
                        # would capture possibly-diverged params and rotate
                        # the last good snapshot toward deletion — the very
                        # state rollback needs.  (The observation lag means
                        # a boundary landing within ~2 dispatches of the
                        # first bad step can still be captured; with the
                        # guard on, params are protected regardless.)
                        if (self._fp is not None and
                                step // self.sdc_every
                                > before // self.sdc_every):
                            # dispatch the fingerprint on the state the
                            # step just produced (async — its buffers are
                            # still valid here; the NEXT dispatch's
                            # donation is sequenced after this read), and
                            # observe at lag 2 like the loss monitor.
                            # Runs BEFORE the snapshot block below, so a
                            # corruption this boundary can surface is
                            # handled before anything reaches disk.
                            sdc_q.append((step, self._fp.compute(self.state)))
                            act = sdc_pump(keep=1)
                            if act == "rollback":
                                # cross-host divergence: the local
                                # majority is no reference — restore the
                                # newest verified checkpoint (identical
                                # on every host, DESIGN.md §8 machinery)
                                do_rollback("[sdc] cross-host divergence")
                                break
                        if (cfg.checkpoint_every and
                                step // cfg.checkpoint_every
                                > before // cfg.checkpoint_every and
                                (monitor is None or monitor.consecutive == 0)):
                            # a snapshot must never capture state the
                            # fingerprint queue has not cleared yet: the
                            # corrupt bytes would reach disk and rotate
                            # the last good generation toward deletion —
                            # the SDC analogue of the bad-streak skip
                            # above.  Draining costs nothing extra here:
                            # these futures are older than the state
                            # device_get the save itself stalls on.
                            if sdc_pump(keep=0) == "rollback":
                                do_rollback("[sdc] cross-host divergence "
                                            "at a snapshot boundary")
                                break
                            with watchdog.suspended():
                                self.save()
                    if rolled_back:
                        epoch = (step + self._step_offset) // spe
                        mid_epoch_start = (step + self._step_offset) % spe
                        continue
                    if shutdown.requested:
                        # graceful preemption: materialize the last loss, then
                        # fall through to the final save with <= 1 lost step
                        if loss is not None:
                            last_loss = float(jax.device_get(loss))
                        break
                    # per-epoch loss line (reference :224, but one global line
                    # instead of N interleaved per-rank prints)
                    if loss is not None:
                        last_loss = float(jax.device_get(loss))
                    log(f"epoch {epoch + 1}: loss {last_loss:.6f} "
                        f"({time.perf_counter() - epoch_t0:.3f}s)")
                    # periodic held-out eval (the reference's :213-220 intent)
                    if (self.val_data is not None and cfg.eval_every
                            and (epoch + 1) % cfg.eval_every == 0):
                        with trace_lib.span("eval", epoch=epoch), \
                                watchdog.suspended():
                            ev = self.evaluate(self.val_data)
                        last_eval = (step, ev)
                        log("validation: " + ", ".join(
                            f"{k} {v:.6f}" for k, v in sorted(ev.items())))
                        self.metrics.write({"step": step, "epoch": epoch,
                                            **{f"val_{k}": v
                                               for k, v in ev.items()}})
                    epoch += 1
                # drain the SDC lag queue before the final save: every
                # queued fingerprint is complete by now, and a divergence
                # detected here must still heal (or abort) BEFORE the
                # final snapshot can capture corrupt state
                sdc_pump(keep=0, draining=True)
        finally:
            # deterministic prefetch-worker release: an exception escaping
            # this frame (AnomalyAbort, a re-raised async-write failure)
            # keeps it alive in the traceback, so the abandoned dispatch
            # generator would otherwise park its loader thread until GC
            if dispatches is not None and hasattr(dispatches, "close"):
                dispatches.close()
            for line in laps.end():
                log(line, every_process=True, file=sys.stderr)
            exc = sys.exc_info()[1]
            if exc is not None:
                # abnormal exit (anomaly abort, crash): the flight
                # recorder's dump is the black box a relaunch reads —
                # then release the telemetry/metrics handles (the normal
                # path closes them at the end of fit; without this an
                # aborted fit leaks the jsonl fd and leaves the module
                # _ACTIVE pointing at a dead run's directory)
                self.telemetry.on_abnormal_exit(exc)
                self.metrics.close()
                self.telemetry.close()
                if self.tracer is not None:
                    # flush the span timeline too: the trace must
                    # survive the crash for the postmortem merge
                    trace_lib.stop_run(self.tracer)
        if prev is not None and cfg.log_every and \
                prev[0] // cfg.log_every > prev[3] // cfg.log_every:
            self.metrics.write({"step": prev[0], "epoch": prev[1],
                                "loss": last_loss,
                                "samples_per_sec": thr.samples_per_sec})
        # drain the telemetry lag queue (every queued future is complete
        # by now) and write the final heartbeat at the real step (in the
        # heartbeat-only metrics_every=0 mode no record carries one)
        self.telemetry.flush(step=step)
        if shutdown.requested:
            self.telemetry.on_preempted(shutdown.signum, step)
        self.save(final=True)
        result = {"final_loss": last_loss,
                  "steps": step,
                  "samples_per_sec": thr.samples_per_sec,
                  **timer.stats()}
        if shutdown.requested:
            # preemption-safe exit: the final save above already drained
            # pending async writes and snapshotted the current step — an
            # external restart (--resume / the supervisor) loses <= 1 step
            if shutdown.noticed:
                # ADVANCE-notice preemption (SIGUSR1): the node is going
                # away — the cli maps this to EXIT_DECOMMISSION (47), a
                # terminal no-retry exit the goodput ledger prices as
                # drain (the coordinated-shrink path, DESIGN.md §10:
                # peers of a multi-host victim lose it and ride the
                # elastic probe-and-shrink relaunch from THIS snapshot
                # instead of rolling back)
                log(f"preemption notice (signal {shutdown.signum}, grace "
                    f"{shutdown.grace_s or 0:.1f}s): final checkpoint at "
                    "step "
                    f"{step}, exiting 47 (decommission)")
                result["preempt_notice"] = True
            else:
                log(f"preempted (signal {shutdown.signum}): final "
                    f"checkpoint at step {step}, exiting 0")
            result["preempted"] = True
        if monitor is not None:
            result["rollbacks"] = monitor.rollbacks
            result["bad_steps"] = monitor.bad_steps
        if self._sdc_policy is not None:
            result["sdc_incidents"] = self._sdc_policy.incidents
            result["sdc_healed"] = self._sdc_policy.healed
        if self.guarded:
            # GuardedState.skipped: cumulative rejected updates — read
            # once here, off the hot path
            result["skipped_updates"] = int(
                jax.device_get(self.state.opt_state.skipped))
        # achieved model FLOPs/s (fwd + ~2x bwd per optimizer step), from
        # the single-source analytic accounting (train.telemetry /
        # Module.fwd_flops) — None for unaccounted architectures
        sample_shape = (1,) + tuple(self.data["x"].shape[1:])
        step_flops = telemetry_lib.train_step_flops(self.model, sample_shape)
        if step_flops is not None:
            result["model_flops_per_sec"] = step_flops * thr.samples_per_sec
            if self.telemetry.enabled:
                # a utilization exists against a chip's peak only
                peak = self.telemetry.peak_total
                result["mfu"] = (None if peak is None else
                                 result["model_flops_per_sec"] / peak)
        # peak device memory where the backend reports it (TPU HBM; {} on
        # CPU) — the observability the reference's prints never had.
        # PROCESS-lifetime high-water mark (the runtime never resets it),
        # so a second fit() in one process inherits the first's peak —
        # hence the explicit key name.
        mem = profiling.device_memory_stats()
        peaks = [v.get("peak_bytes_in_use") for v in mem.values()
                 if "peak_bytes_in_use" in v]
        if peaks:
            result["process_peak_memory_bytes"] = max(peaks)
        # post-training held-out eval (the reference's :227-236 intent);
        # reuse the periodic eval when it already ran at this exact step
        if self.val_data is not None:
            if last_eval is not None and last_eval[0] == step:
                ev = last_eval[1]
            else:
                with trace_lib.span("eval", final=True):
                    ev = self.evaluate(self.val_data)
                self.metrics.write({"step": step, "final": True,
                                    **{f"val_{k}": v for k, v in ev.items()}})
            result.update({f"val_{k}": v for k, v in ev.items()})
        self.metrics.close()
        self.telemetry.close()
        if self.tracer is not None:
            trace_lib.stop_run(self.tracer)
        return result

    def _eval_params(self):
        """Params in the *dense* (per-layer, unpermuted) layout — used for
        checkpoint interop and tests, NOT by :meth:`evaluate` (every eval
        step consumes the train state's own layout in place, so this
        single-host gather is off the eval path entirely)."""
        if not (self.pipeline or self.sp_tp or self.ep_tp):
            return self.state.params
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel import pipeline as pp

        params = dict(jax.device_get(self.state.params))
        params["blocks"] = pp.dense_layer_blocks(
            params["blocks"], self.model.cfg,
            saved_tp=int(self.mesh.shape.get("tensor", 1)))
        return jax.device_put(params, NamedSharding(self.mesh, P()))

    def evaluate(self, data: Optional[Dict[str, np.ndarray]] = None) -> Dict[str, float]:
        loader = self.loader if data is None else ShardedLoader(
            self.mesh, data, self.cfg.batch_size, shuffle=False,
            seed=self.cfg.seed, full_batch=self.cfg.full_batch,
            seq_axis="seq" if self.seq_parallel else None,
            batch_axes=self.batch_axes,
            seq_permutation=self.seq_permutation)
        # every eval step (dense, gspmd, moe, pipelined) consumes the train
        # state's own layout in place — no gather; _eval_params is only for
        # checkpoint interop / dense export
        params = self.state.params
        sums: Dict[str, float] = {}
        totals: Dict[str, float] = {}
        for batch in loader.epoch(0):
            # eval emits no train dispatches; beat the heartbeat so the
            # external staleness monitor doesn't kill a long eval tail
            self.telemetry.alive()
            m = jax.device_get(self.eval_step(params, batch))
            c = float(m.pop("count"))
            ec = float(m.pop("example_count", c))
            for k, v in m.items():
                w = ec if k == "accuracy" else c  # per-example vs per-token
                sums[k] = sums.get(k, 0.0) + float(v) * w
                totals[k] = totals.get(k, 0.0) + w
        out = {k: v / totals[k] for k, v in sums.items()}
        if self.cfg.loss == "cross_entropy" and "loss" in out:
            # token-level perplexity (the LM community's headline number);
            # clamp the exponent so a huge-but-finite loss can't overflow
            # to inf (a NaN loss stays NaN — same signal as val_loss)
            out["ppl"] = float(np.exp(min(out["loss"], 30.0))
                               if not np.isnan(out["loss"])
                               else float("nan"))
        return out
