"""The JAX training executor — TPU-native replacement for the reference's
Catalyst executor (reference worker/executors/catalyst/catalyst.py:29-379).

Capability parity map:
- config-driven model/optimizer/stages       → catalyst.py Args/config
- per-epoch metric series + best score to DB → on_epoch_end,
  catalyst.py:100-145
- hierarchical steps per stage/epoch         → catalyst.py:86-98
- grid-cell merge                            → catalyst.py:177-179 (done
  upstream in Executor.from_config)
- checkpoint save/resume w/ stage arithmetic → catalyst.py:218-296
- one-stage-per-dispatch + requeue           → catalyst.py:354-368 +
  worker/tasks.py:215-236
- distributed training                       → mesh + shardings instead of
  MASTER_ADDR/RANK env vars (catalyst.py:195-207); the supervisor hands
  the task a mesh spec, XLA handles the collectives

Example spec::

    train:
      type: jax_train
      model: {name: resnet18, num_classes: 10, dtype: bfloat16}
      dataset: {name: synthetic_images}
      loss: softmax_ce
      batch_size: 128
      mesh: {dp: -1}
      stages:
        - {name: stage1, epochs: 3, optimizer: {name: adam, lr: 1e-3}}
      main_metric: accuracy
      minimize: false
      model_name: my_model      # optional Model-registry entry
"""

import contextlib
import dataclasses
import functools
import itertools
import json
import math
import os
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from mlcomp_tpu.models import create_model, param_count
from mlcomp_tpu.parallel import (
    batch_sharding, data_parallel_size, mesh_from_spec,
)
from mlcomp_tpu.train.checkpoint import (
    load_meta, restore_checkpoint, resume_plan, save_checkpoint,
)
from mlcomp_tpu.train.data import (
    create_dataset, iterate_batches, place_batch, prefetch_batches,
)
from mlcomp_tpu.train.loop import (
    SELF_SUPERVISED, STEP_COUNTERS, aggregate_metrics, create_train_state,
    loss_for_task, make_eval_step,
    make_train_step,
)
from mlcomp_tpu.train.optim import make_optimizer
from mlcomp_tpu.worker.executors import Executor


def _phase(name):
    """The decorated method of ``JaxTrain`` is the phase ``name``: it
    runs inside the span of that name (``JaxTrain._span``)."""
    def wrap(method):
        @functools.wraps(method)
        def inside(self, *args, **kwargs):
            with self._span(name):
                return method(self, *args, **kwargs)
        return inside
    return wrap


@dataclasses.dataclass
class _Inputs:
    """What ``train.setup.data`` leaves: the data set on the host and,
    on the device-data path, resident on the mesh with the on-device
    augmentation; on the host path the host ``transform``."""
    x_train: Any
    y_train: Any
    x_valid: Any
    y_valid: Any
    seq_dim: Optional[int]
    use_device_data: bool
    x_all: Any = None
    y_all: Any = None
    xv_all: Any = None
    yv_all: Any = None
    dequant: bool = False
    dequant_v: bool = False
    dev_augment: Optional[Callable] = None
    transform: Any = None


@dataclasses.dataclass
class _Run:
    """What the phases of one job share, and what they move forward:
    the train state, the best score, the count of epochs."""
    mesh: Any
    ck_dir: str
    loss_fn: Callable
    self_supervised: bool
    steps_per_epoch: int
    model: Any = None
    state: Any = None
    n_params: int = 0
    meta: Optional[dict] = None         # of the checkpoint restored
    best: Optional[float] = None
    first_global_epoch: int = 0         # where this dispatch resumed
    global_epoch: int = 0
    images_seen: int = 0


@dataclasses.dataclass
class _Stage:
    name: str
    epochs: int
    optimizer: Any
    train_step: Callable    # (state, *feed) -> (state, metrics)
    evaluate: Callable      # (state, rows, weights) -> metrics


@Executor.register
class JaxTrain(Executor):
    def __init__(self, model=None, dataset=None, loss='softmax_ce',
                 batch_size=32, eval_batch_size=None, mesh=None,
                 stages=None, epochs=1, optimizer=None,
                 main_metric='accuracy', minimize=False,
                 model_name=None, seed=0, checkpoint_dir=None,
                 stage_per_dispatch=False, report_imgs=None,
                 augment=None, prefetch=2, device_data='auto',
                 checkpoint_every=1, infer_valid=None, profile=None,
                 async_checkpoint=True, telemetry=True, **kwargs):
        self.model_spec = dict(model or {'name': 'mlp'})
        # pretrained init (reference contrib/model/pretrained.py:6-59
        # head-swap): popped so create_model and the export .json see
        # architecture args only
        self.params_file = self.model_spec.pop('params_file', None)
        self.dataset_spec = dict(dataset or {})
        # loss may be a name or a dict spec ({name: lm_ce, z_loss: ..,
        # label_smoothing: ..} routes through the fused CE kernel)
        self.loss_spec = dict(loss) if isinstance(loss, dict) else loss
        self.loss_name = loss.get('name') if isinstance(loss, dict) \
            else loss
        self.batch_size = int(batch_size)
        self.eval_batch_size = int(eval_batch_size or batch_size)
        self.mesh_spec = mesh
        self.stages = [dict(s) for s in (stages or [])] or [
            {'name': 'stage1', 'epochs': int(epochs),
             'optimizer': optimizer or {'name': 'adam', 'lr': 1e-3}}]
        self.main_metric = main_metric
        self.minimize = bool(minimize)
        self.model_name = model_name
        self.seed = int(seed)
        self.checkpoint_dir = checkpoint_dir
        self.stage_per_dispatch = bool(stage_per_dispatch)
        self.report_imgs = dict(report_imgs) if report_imgs else None
        self.augment = list(augment) if augment else None
        self.prefetch = int(prefetch)
        self.device_data = device_data
        self.checkpoint_every = int(checkpoint_every)
        if self.checkpoint_every == 0:
            wants_best = bool(infer_valid) and \
                bool(dict(infer_valid).get('best_only', True))
            if stage_per_dispatch or model_name or wants_best:
                raise ValueError(
                    'checkpoint_every: 0 disables saving, but '
                    'stage_per_dispatch requeue, model_name export, '
                    'and infer_valid best_only (its default) all read '
                    'checkpoint files — drop one, or set '
                    'infer_valid: {best_only: false}')
        # {'out_prefix': str, 'best_only': bool} — dump validation
        # predictions as npy after training (the flax analogue of the
        # reference's InferBestCallback,
        # contrib/catalyst/callbacks/inference.py:10-50)
        self.infer_valid = dict(infer_valid) if infer_valid else None
        # background-thread checkpoint writes: the epoch's compute
        # overlaps serialise+disk instead of stalling on them (the
        # device→host gather stays synchronous — it's a collective)
        self.async_checkpoint = bool(async_checkpoint)
        # {'epoch': N | 'epochs': [..], 'dir': path} — capture an XLA
        # device trace (XProf/TensorBoard format) for the given global
        # epoch(s). The TPU-native profiler: where the reference leans
        # on Catalyst's host-side timers (SURVEY §5 tracing substitutes)
        # this records the real device timeline incl. fusion + HBM
        self.profile = dict(profile) if profile else None
        # telemetry: True (default) | False | {flush_every: N,
        # cost_analysis: bool, memory_analysis: bool,
        # collectives: bool, memory_every: N, peak_tflops: float,
        # profile_every: N, profile_steps: N}.
        # Per-step loss/throughput series + the per-step HBM timeline
        # (MemorySampler, memory_every cadence) + per-epoch device
        # stats land in the metric table (telemetry/); cost_analysis/
        # memory_analysis/collectives share ONE AOT lowering of the
        # step for XLA's FLOPs count, the static peak-memory
        # attribution, and the collective-communication tally + wire
        # probe — each defaults on off-CPU only (the lowering is an
        # extra compile the CPU test harness shouldn't pay)
        self.telemetry_spec = dict(telemetry) \
            if isinstance(telemetry, dict) else ({} if telemetry else None)
        # leftover config keys: NOT an error (forward-compat), but a
        # silent swallow turns typos and non-matching grid-cell keys
        # into no-op sweeps — _work logs them loudly
        self._unknown_kwargs = sorted(kwargs)

    # ------------------------------------------------------------ plumbing
    def _init_distributed(self):
        """Join a multi-host job when this is a fanned-out service task
        (reference catalyst.py:195-207). ExecuteBuilder normally does this
        before the executor is built; doing it here too covers direct
        invocation (tests, notebooks). Returns True on rank 0."""
        from mlcomp_tpu.parallel.distributed import (
            initialize_from_distr_info, is_main_process,
        )
        info = dict(getattr(self, 'additional_info', None) or {})
        initialize_from_distr_info(info.get('distr_info'))
        return is_main_process()

    @staticmethod
    def _placement(mesh, state, batch_shape, seq_dim) -> dict:
        """How the mesh splits a batch and the most-split parameter:
        the devices holding a shard of each and the share of its bytes
        one device keeps (1.0 = replicated) — what a sharded run must
        show before its numbers mean anything. Read from the arrays'
        shardings: touching ``addressable_shards`` would leave aliases
        of buffers the train step is about to donate."""
        def local_frac(sharding, shape):
            return math.prod(sharding.shard_shape(tuple(shape))) \
                / max(1, math.prod(shape))

        batch = batch_sharding(mesh, len(batch_shape), seq_dim=seq_dim)
        leaf = min(jax.tree.leaves(state.params),
                   key=lambda a: local_frac(a.sharding, a.shape))
        return {
            'batch_devices': len(batch.device_set),
            'batch_local_frac': local_frac(batch, batch_shape),
            'param_devices': len(leaf.sharding.device_set),
            'param_local_frac': local_frac(leaf.sharding, leaf.shape),
            'param_shape': list(leaf.shape)}

    def _mesh(self):
        spec = self.mesh_spec
        if spec is None:
            spec = {'dp': -1}
        info = dict(getattr(self, 'additional_info', None) or {})
        distr = info.get('distr_info') or {}
        # the supervisor may pin the mesh for the whole fanned-out job
        if distr.get('mesh'):
            spec = distr['mesh']
        devices = None
        if spec and not distr.get('mesh') \
                and all(int(v) != -1 for v in spec.values()):
            # a fully-pinned mesh smaller than the visible device set
            # takes a prefix — the in-process `execute` debug path has
            # no supervisor to restrict cores, but the config's intent
            # (exactly product-many chips) is unambiguous. Only when
            # the supervisor did NOT pin the mesh: for a fanned-out
            # job a size mismatch is a placement bug that must stay a
            # loud normalize_mesh_spec error, not a silent prefix
            product = math.prod(int(v) for v in spec.values())
            visible = jax.devices()
            if 0 < product < len(visible):
                devices = visible[:product]
        return mesh_from_spec(spec, devices=devices)

    def _checkpoint_folder(self):
        if self.checkpoint_dir:
            return self.checkpoint_dir
        from mlcomp_tpu import TASK_FOLDER
        task_id = self.task.id if self.task else 0
        # service tasks of one distributed job share the PARENT's folder
        # so every rank sees the same resume state (reference fetches the
        # master's checkpoint, catalyst.py:244-249; here: shared dir on
        # one host, FileSync across hosts)
        if self.task is not None and self.task.parent:
            task_id = self.task.parent
        return os.path.join(TASK_FOLDER, str(task_id), 'checkpoints')

    def _report_series(self, name, value, epoch, part, stage):
        if self.session is None or self.task is None:
            return
        if not getattr(self, '_is_main', True):
            return
        from mlcomp_tpu.db.models import ReportSeries
        from mlcomp_tpu.db.providers import ReportSeriesProvider
        from mlcomp_tpu.utils.misc import now
        ReportSeriesProvider(self.session).add(ReportSeries(
            task=self.task.id, time=now(), epoch=int(epoch),
            value=float(value), name=name, part=part, stage=stage))

    def _sweep_info(self):
        info = dict(getattr(self, 'additional_info', None) or {})
        sweep = info.get('sweep')
        return dict(sweep) if isinstance(sweep, dict) else None

    def _report_sweep(self, global_epoch: int, steps_per_epoch: int,
                      score) -> bool:
        """ASHA rung reporting for sweep cells (contrib/search/asha.py
        contract): one ``sweep.score`` row per epoch boundary, budget
        in the sweep's unit, attributed to the CELL task (the parent
        for a fanned-out distributed cell — the supervisor judges
        cells, not ranks). Returns True when this epoch ended exactly
        ON a rung boundary, which is the train loop's cue to force a
        checkpoint there. Best-effort like every observability write.
        """
        sweep = self._sweep_info()
        if sweep is None or self.session is None or self.task is None:
            return False
        if not getattr(self, '_is_main', True):
            return False
        from mlcomp_tpu.contrib.search.asha import (
            report_sweep_score, rung_boundaries,
        )
        epochs_done = global_epoch + 1
        per_epoch = 1 if sweep.get('unit', 'epochs') == 'epochs' \
            else int(steps_per_epoch)
        budget = epochs_done * per_epoch
        if score is not None:
            cell_id = self.task.parent or self.task.id
            report_sweep_score(self.session, cell_id, budget, score)
        try:
            base = int(sweep.get('base') or sweep.get('rung_epochs', 1))
            eta = float(sweep.get('eta', 2))
        except (TypeError, ValueError):
            return False
        # "crossed this epoch", not exact membership: step-unit rung
        # boundaries generically fall MID-epoch (rung_steps=100 with 64
        # steps/epoch), and the checkpoint contract is per-boundary,
        # not per-exact-hit
        prev_budget = budget - per_epoch
        return any(prev_budget < b <= budget
                   for b in rung_boundaries(base, eta, budget))

    def _update_scores(self, score):
        """task.score + Model.score_local best tracking
        (reference catalyst.py:131-145, valid.py:74-81)."""
        if self.session is None or self.task is None:
            return
        if not getattr(self, '_is_main', True):
            return
        from mlcomp_tpu.db.providers import ModelProvider, TaskProvider
        better = (self.task.score is None or
                  (score < self.task.score if self.minimize
                   else score > self.task.score))
        if better:
            self.task.score = float(score)
            TaskProvider(self.session).update(self.task, ['score'])
            if self.model_name:
                from mlcomp_tpu.db.models import Model
                from mlcomp_tpu.utils.misc import now
                provider = ModelProvider(self.session)
                row = provider.by_name(self.model_name)
                if row is None:
                    row = Model(
                        name=self.model_name, project=self.dag.project,
                        dag=self.dag.id, created=now())
                row.score_local = float(score)
                provider.create_or_update(row, 'name')

    # ---------------------------------------------------------------- work
    def work(self):
        self._ckpt_writer = None
        self._profile_open = False
        self._telemetry = None
        self._profiler = None
        self._deviceprof = None
        self._attribution = None
        self._tripwire = None
        self._compile_events = None
        self._memory = None
        self._step_flops = None
        self._comm_probe_ms = None
        self._introspected = False
        ok = False
        # the train loop's leg of the cross-process trace: a
        # `train.work` root (role='train') with the set-up and
        # per-epoch phase spans below it (_span), joined to the
        # supervisor dispatch and worker pipeline spans by the trace id
        # the task environment / additional_info carries
        # (telemetry/spans.py trace context)
        self._spans_on = self.telemetry_spec is not None \
            and self.session is not None \
            and getattr(self, 'task', None) is not None
        if self._spans_on:
            from mlcomp_tpu.telemetry import spans
            info = dict(getattr(self, 'additional_info', None) or {})
            self._span_trace_id = info.get('trace_id') or None
            # one clock with the device trace: from here on every span
            # of this process is also an event on the host line of any
            # jax.profiler trace (spans.py stays importable without jax)
            spans.set_annotation_factory(jax.profiler.TraceAnnotation)
        try:
            with self._span('train.work',
                            model=self.model_spec.get('name')):
                result = self._work()
            ok = True
            return result
        finally:
            if self._spans_on:
                from mlcomp_tpu.telemetry import flush_spans
                try:
                    flush_spans(self.session)
                except Exception:
                    pass
            if self._compile_events is not None:
                # a persistent worker's NEXT task must not inherit this
                # task's compile listener (it would record into a
                # closed recorder under a stale task id)
                try:
                    self._compile_events.uninstall()
                except Exception:
                    pass
            if self._profiler is not None:
                try:
                    self._profiler.close()
                except Exception:
                    pass
            if self._deviceprof is not None:
                # an open sampled window stops + parses here so its
                # devtime.* rows land even on the failure path (the
                # postmortem bundle tails them)
                try:
                    self._deviceprof.close()
                except Exception:
                    pass
            if self._telemetry is not None:
                try:
                    self._telemetry.close()
                except Exception:
                    pass
            if self._profile_open:
                # an exception mid-epoch skipped _stop_profile; close the
                # trace so a restarted executor can start a new one
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                self._profile_open = False
            writer, self._ckpt_writer = self._ckpt_writer, None
            if writer is not None:
                try:
                    writer.close()
                except Exception as e:
                    self.error(f'checkpoint writer: {e}')
                    # on the failure path keep the original training
                    # exception; the writer error is logged above
                    if ok:
                        raise

    def _span(self, name, **tags):
        """``with self._span(name):`` is ``name`` as a child of the
        innermost open span (the DB row, and the profiler annotation:
        telemetry/spans.py); an exception that leaves the block closes
        it, and then its parents, as errors. Nothing is opened in a run
        without telemetry."""
        if not self._spans_on:
            return contextlib.nullcontext()
        from mlcomp_tpu.telemetry import span
        return span(name, task=self.task.id, role='train',
                    trace_id=self._span_trace_id, tags=tags or None)

    def _drain_ckpt_writer(self):
        if self._ckpt_writer is not None:
            self._ckpt_writer.wait()

    def _work(self):
        t_start = time.time()
        if self._unknown_kwargs:
            self.info(
                f'WARNING: config keys {self._unknown_kwargs} match '
                f'nothing in jax_train — a typo, or a grid-cell key '
                f'whose suffix path does not reach the spec (lists '
                f'like stages: are opaque to the merge)')
        self._is_main = self._init_distributed()
        if self._is_main and self.async_checkpoint:
            from mlcomp_tpu.train.checkpoint import AsyncCheckpointWriter
            self._ckpt_writer = AsyncCheckpointWriter()
        mesh = self._mesh()
        # where this run happens, once, so the DB shows it (a chip of a
        # host split between processes is named by TPU_VISIBLE_CHIPS:
        # each such process numbers its own devices from 0)
        devices = list(mesh.devices.flat)
        self.info('devices: ' + json.dumps({
            'platform': devices[0].platform,
            'kind': devices[0].device_kind,
            'ids': [d.id for d in devices],
            'visible_chips': os.environ.get('TPU_VISIBLE_CHIPS', 'all'),
            'mesh': {k: int(v) for k, v in dict(mesh.shape).items()}}))

        # set-up, each phase a span (a child of train.work, like the
        # epochs below); a third, `introspect`, is the AOT compile of
        # the first stage's step
        self_supervised = self.loss_name in SELF_SUPERVISED
        inputs = self._setup_data(mesh, self_supervised)
        run = _Run(
            mesh=mesh, ck_dir=self._checkpoint_folder(),
            loss_fn=loss_for_task(self.loss_spec),
            self_supervised=self_supervised,
            steps_per_epoch=max(
                1, len(inputs.x_train) // self.batch_size))
        self._setup_telemetry(run.ck_dir)
        self._setup_state(run, inputs)

        # stage-per-dispatch (distributed parity, catalyst.py:354-368):
        # the task's additional_info names the stage this dispatch runs
        info = dict(getattr(self, 'additional_info', None) or {})
        dispatch_stage = info.get('stage') if self.stage_per_dispatch \
            else None
        stage_names = [s['name'] for s in self.stages]
        remaining, start_epoch = resume_plan(self.stages, run.meta)
        if dispatch_stage is not None:
            remaining = [s for s in remaining
                         if s['name'] == dispatch_stage] or remaining[:1]
        for spec in remaining:
            stage = self._setup_stage(run, inputs, spec)
            first_epoch = start_epoch if spec is remaining[0] else 0
            if first_epoch == 0 and spec is not self.stages[0]:
                # stage boundary: fresh optimizer state, keep params
                # (resuming mid-stage keeps the restored opt state)
                run.state = run.state.replace(
                    opt_state=stage.optimizer.init(run.state.params))
            self.step.start(1, f'stage {stage.name}',
                            stage_names.index(stage.name))
            for epoch in range(first_epoch, stage.epochs):
                self._epoch(run, inputs, stage, epoch)
            if (dispatch_stage is not None or self.stage_per_dispatch) \
                    and stage.name != stage_names[-1]:
                # return for requeue: next dispatch runs the next stage.
                # The LAST stage's dispatch falls through instead so the
                # model export / report-img pass still runs.
                self._drain_ckpt_writer()   # requeued stage reads last
                return {'stage': stage.name, 'stages': stage_names,
                        'best_score': run.best}

        # everything below reads checkpoint files — drain pending writes
        self._drain_ckpt_writer()
        x_train, x_valid = inputs.x_train, inputs.x_valid
        if self._is_main and self.model_name:
            self._export_model(run.ck_dir, run.best,
                               input_shape=[int(d) for d in
                                            x_train.shape[1:]],
                               input_dtype=str(x_train.dtype))
        # the post-train passes run collective programs (valid forward,
        # checkpoint gather) — EVERY rank must execute the same sequence;
        # only rank 0 touches DB/filesystem inside each helper
        if self.report_imgs and self.session is not None \
                and self.task is not None:
            self._build_report_imgs(run.model, run.state, mesh, x_valid,
                                    inputs.y_valid,
                                    max(run.global_epoch - 1, 0))
        if self.infer_valid:
            self._infer_valid(run.model, run.state, mesh, run.ck_dir,
                              x_valid, inputs.y_valid)

        wall = time.time() - t_start
        return {'stage': stage_names[-1], 'stages': stage_names,
                'best_score': run.best, 'n_params': run.n_params,
                'wall_time_s': wall,
                'samples_per_sec': run.images_seen / max(wall, 1e-9)}

    # --------------------------------------------------------------- set-up
    @_phase('train.setup.data')
    def _setup_data(self, mesh, self_supervised) -> _Inputs:
        data = create_dataset(**self.dataset_spec) \
            if self.dataset_spec.get('name') else \
            create_dataset('synthetic_images')
        x_train, y_train = data['x_train'], data['y_train']
        x_valid, y_valid = data['x_valid'], data['y_valid']
        seq_dim = 1 if self_supervised and 'sp' in mesh.axis_names else None

        # input path selection: device-resident dataset (HBM) with
        # on-device augmentation when possible — per-step host→device
        # traffic drops from the batch to an index vector — else the
        # host pipeline (vectorized augment + double-buffered transfer)
        from mlcomp_tpu.train.device_data import (
            DEVICE_AUGMENTS, dataset_fits_hbm, make_device_augment,
            normalize_augment_spec, place_dataset, quantize_dataset,
        )
        device_augs = normalize_augment_spec(self.augment)
        if self.device_data is True and device_augs is None:
            raise ValueError(
                f'device_data: true but augment={self.augment!r} has '
                f'transforms outside the device-expressible set '
                f'{DEVICE_AUGMENTS}; drop them or use device_data: auto '
                f'(which falls back to the host pipeline)')
        if self.device_data is True and (y_train is None
                                         or self_supervised):
            raise ValueError(
                'device_data: true supports labeled datasets only — '
                'label-less/self-supervised training uses the host '
                'pipeline (device_data: auto selects it automatically)')
        inputs = _Inputs(
            x_train=x_train, y_train=y_train, x_valid=x_valid,
            y_valid=y_valid, seq_dim=seq_dim,
            use_device_data=(
                self.device_data is True
                or (self.device_data == 'auto'
                    and device_augs is not None
                    and y_train is not None
                    and not self_supervised
                    and seq_dim is None
                    # train AND valid both become HBM-resident
                    and dataset_fits_hbm(x_train,
                                         extra_bytes=x_valid.nbytes))))
        if inputs.use_device_data:
            x_q, inputs.dequant = quantize_dataset(x_train)
            inputs.x_all, inputs.y_all = place_dataset(x_q, y_train, mesh)
            xv_q, inputs.dequant_v = quantize_dataset(x_valid)
            inputs.xv_all, inputs.yv_all = place_dataset(
                xv_q, y_valid, mesh)
            if device_augs:
                inputs.dev_augment = make_device_augment(
                    device_augs, x_train.shape[1:])
        elif self.augment:
            from mlcomp_tpu.contrib.transform import parse_transforms
            inputs.transform = parse_transforms(self.augment)
        return inputs

    def _setup_telemetry(self, ck_dir):
        """Per-step series recorder + on-demand profiler control (rank
        0 only — one writer per task, like _report_series). The
        recorder's hot path is a list append; device values pull at
        flush (every flush_every steps and at each epoch boundary)."""
        if self.telemetry_spec is None or self.session is None \
                or self.task is None or not self._is_main:
            return
        from mlcomp_tpu.telemetry import (
            CompileEventRecorder, DeviceProfiler, HostSyncTripwire,
            MemorySampler, MetricRecorder, StepAttribution, TaskProfiler,
        )
        from mlcomp_tpu.telemetry.deviceprof import (
            DEFAULT_EVERY, DEFAULT_WINDOW,
        )
        # async_flush: the window-full auto-flush (device pull +
        # DB write) runs on a background thread, never inside the
        # wrapped train step
        self._telemetry = MetricRecorder(
            session=self.session, task=self.task.id,
            component='train', async_flush=True,
            flush_every=int(
                self.telemetry_spec.get('flush_every', 100)))
        self._profiler = TaskProfiler(self.session, self.task.id,
                                      ck_dir)
        # step-time attribution + runtime recompile/host-sync
        # detection ride the same recorder: phase marks are clock
        # reads at boundaries the loop already crosses, the
        # compile listener fires only when XLA actually compiles
        # (no-op install on builds without jax.monitoring)
        self._attribution = StepAttribution(recorder=self._telemetry)
        self._tripwire = HostSyncTripwire(recorder=self._telemetry)
        self._compile_events = CompileEventRecorder(
            recorder=self._telemetry)
        self._compile_events.install()
        # per-step HBM timeline (telemetry/memory.py): resolves
        # "does this platform report memory at all" ONCE — inert
        # on CPU, one allocator-stats read per device on TPU. The
        # watchdog's OOM predictor and the postmortem bundle both
        # read the series it emits.
        self._memory = MemorySampler(
            self._telemetry,
            every=int(self.telemetry_spec.get('memory_every', 1)))
        # sampled device-time profiling (telemetry/deviceprof.py):
        # like the introspection gates, default ON off-CPU only —
        # `profile_every: <steps>` in the telemetry spec forces it
        # either way (0 disables); `profile_steps` sets the window
        # extent in dispatches
        prof_every = self.telemetry_spec.get('profile_every')
        if prof_every is None:
            prof_every = DEFAULT_EVERY \
                if jax.default_backend() != 'cpu' else 0
        if int(prof_every) > 0:
            self._deviceprof = DeviceProfiler(
                self.session, self.task.id,
                every=int(prof_every),
                window=int(self.telemetry_spec.get(
                    'profile_steps', DEFAULT_WINDOW)),
                logger=self.info)

    def _want(self, key):
        """Per-feature introspection gate: 'cost_analysis' /
        'memory_analysis' / 'collectives' / 'op_blocks' each default ON
        off-CPU only (the shared AOT lowering is an extra compile the
        CPU test harness shouldn't pay) and can be forced either way
        in the telemetry spec."""
        want = self.telemetry_spec.get(key)
        if want is None:
            want = jax.default_backend() != 'cpu'
        return bool(want)

    def _abstract_step_args(self, run, inputs):
        """The train step's arguments after the state, for the
        introspection compile. The abstract batch carries the REAL
        input shardings: an unsharded (replicated) one compiles a
        collective-free program — every device would own the whole
        batch, no gradient psum — and the collective tally/probe would
        certify zero comm for a step whose production twin all-reduces
        every grad."""
        mesh = run.mesh
        if inputs.use_device_data:
            return (inputs.x_all, inputs.y_all, jax.ShapeDtypeStruct(
                (self.batch_size,), np.int32,
                sharding=batch_sharding(mesh, 1)))
        x_train, y_train = inputs.x_train, inputs.y_train
        return (
            jax.ShapeDtypeStruct(
                (self.batch_size,) + x_train.shape[1:], x_train.dtype,
                sharding=batch_sharding(
                    mesh, 1 + len(x_train.shape[1:]),
                    seq_dim=inputs.seq_dim)),
            None if y_train is None else jax.ShapeDtypeStruct(
                (self.batch_size,) + y_train.shape[1:], y_train.dtype,
                sharding=batch_sharding(
                    mesh, 1 + len(y_train.shape[1:]))))

    def _introspect(self, mesh, step_fn, *abstract_args):
        """Compiled-step introspection, once per run off ONE AOT
        lower+compile: XLA cost analysis (the in-loop half of
        bench's MFU), static peak memory attribution
        (telemetry/memory.py), and the collective-communication
        tally + measured wire probe (telemetry/collectives.py), and
        the block of every op of the step (telemetry/op_blocks.py).
        The ``_introspected`` latch stops later stages from paying
        the lowering again even when a backend offers none of the
        analyses."""
        if self._telemetry is None or self._introspected:
            return
        wants = {key: self._want(key) for key in
                 ('cost_analysis', 'memory_analysis', 'collectives',
                  'op_blocks')}
        if not any(wants.values()):
            return
        self._introspected = True
        with self._span('train.setup.introspect'):
            try:
                compiled = step_fn.lower(*abstract_args).compile()
            except Exception as e:
                self.info(f'telemetry: step introspection skipped '
                          f'({e})')
                return
            # how many Pallas kernels the step runs: says whether a
            # save-by-name `remat` policy (models/qwen3_next.py) took
            # the forward kernels out of the backward pass
            text = compiled.as_text()
            self._telemetry.gauge('step.kernel_calls',
                                  text.count('tpu_custom_call'))
            # and how many copies the flash op's layout code left in it:
            # says whether the kernels read q, k, v as the projections
            # lay them out (ops/flash_attention.py, LAYOUT_SCOPE)
            from mlcomp_tpu.ops.flash_attention import layout_copies
            self._telemetry.gauge('step.flash_layout_copies',
                                  layout_copies(text))
            # and how many scatters of whole rows the routing round the
            # experts left: says whether SparseMoe moved its rows by
            # gathers (models/decoder_parts.py)
            from mlcomp_tpu.telemetry.op_blocks import row_scatters
            self._telemetry.gauge('step.wide_scatters', row_scatters(text))
            if wants['op_blocks']:
                # which model block each op of the step belongs to, for
                # whoever joins a device trace to it (the row
                # ``step.op_blocks``)
                from mlcomp_tpu.telemetry.op_blocks import (
                    op_table, persist_op_table,
                )
                t0 = time.perf_counter()
                table = op_table(text)
                try:
                    persist_op_table(self.session, self.task.id, table,
                                     time.perf_counter() - t0)
                except Exception:
                    pass
            if wants['cost_analysis']:
                try:
                    cost = compiled.cost_analysis()
                    if isinstance(cost, (list, tuple)):
                        cost = cost[0]
                    self._step_flops = \
                        float(cost.get('flops', 0.0)) or 0
                except Exception:
                    self._step_flops = 0
            # everything below is best-effort context, like the
            # run.snapshot write: a transient DB hiccup (the locked-
            # sqlite window the db.execute fault point exists for)
            # during the persist must never fail a HEALTHY training
            # run through the introspection path
            if wants['memory_analysis']:
                from mlcomp_tpu.telemetry import (
                    memory_attribution, persist_memory_attribution,
                )
                attribution = memory_attribution(compiled)
                if attribution:
                    try:
                        persist_memory_attribution(
                            self.session, self.task.id, attribution)
                    except Exception:
                        pass
                    self.info(
                        'memory attribution (compiled peak): '
                        + ', '.join(
                            f'{k.replace("_bytes", "")}='
                            f'{v / 1e9:.2f} GB'
                            for k, v in sorted(attribution.items())))
            if wants['collectives']:
                from mlcomp_tpu.telemetry import (
                    collective_stats, measure_collective_ms,
                    persist_collective_stats,
                )
                try:
                    stats = collective_stats(text)
                except Exception:
                    stats = None
                if stats is not None:
                    self._comm_probe_ms = measure_collective_ms(
                        mesh, stats['total_bytes'])
                    try:
                        persist_collective_stats(
                            self.session, self.task.id, stats,
                            comm_ms=self._comm_probe_ms)
                    except Exception:
                        pass
                    if stats['total_count']:
                        probe = (f', probe '
                                 f'{self._comm_probe_ms:.2f} ms'
                                 if self._comm_probe_ms else '')
                        self.info(
                            f'collectives per step: '
                            f'{stats["total_count"]} ops, '
                            f'{stats["total_bytes"] / 1e6:.1f} MB '
                            f'per device{probe}')

    def _stage_optimizer(self, stage, steps_per_epoch):
        spec = stage.get('optimizer') or self.stages[0].get('optimizer')
        return make_optimizer(
            spec, int(stage.get('epochs', 1)) * steps_per_epoch)[0]

    @_phase('train.setup.state')
    def _setup_state(self, run, inputs):
        """The model and its train state into ``run``: fresh, restored
        from the last checkpoint (reference catalyst.py:218-296), or
        seeded with pretrained weights."""
        mesh, ck_dir, x_train = run.mesh, run.ck_dir, inputs.x_train
        model = run.model = create_model(mesh=mesh, **self.model_spec)
        stage_names = [s['name'] for s in self.stages]
        # Read the checkpoint meta FIRST: the restore target's opt_state
        # structure must match the optimizer of the stage that SAVED the
        # checkpoint, not stages[0] (they can be different optim types).
        meta = load_meta(ck_dir)
        if jax.process_count() > 1:
            # EVERY rank must see the same meta or ranks build different
            # optimizer structures and trim different stages — with the
            # sharded per-host checkpoint format, a rank whose folder
            # missed the index.json sync is the designed-for hazard, so
            # vote BEFORE anything downstream depends on meta
            from jax.experimental import multihost_utils
            stage_idx = stage_names.index(meta['stage']) \
                if meta and meta.get('stage') in stage_names else -1
            votes = multihost_utils.process_allgather(np.array(
                [int(meta is not None), stage_idx,
                 int(meta.get('epoch', -1)) if meta else -1]))
            if not (votes == votes[0]).all():
                raise RuntimeError(
                    f'checkpoint meta differs across hosts '
                    f'({votes.tolist()}) — sync the checkpoint folder '
                    f'(index.json + fragments) before resuming')
        target_stage = self.stages[0]
        if meta and meta.get('stage') in stage_names:
            target_stage = self.stages[stage_names.index(meta['stage'])]
        optimizer = self._stage_optimizer(target_stage,
                                          run.steps_per_epoch)
        # init batch must divide the data-parallel axes (shard_map inside
        # the model sees global shapes during init's forward trace)
        sample = x_train[:max(1, data_parallel_size(mesh))]
        state = create_train_state(
            model, optimizer, sample, jax.random.PRNGKey(self.seed),
            mesh=mesh, with_dropout_rng=True)
        n_params = run.n_params = param_count(state.params)
        self.info(
            f'model={self.model_spec.get("name")} params={n_params:,} '
            f'mesh={dict(mesh.shape)} devices={len(mesh.devices.flat)}')
        self.info('placement: ' + json.dumps(self._placement(
            mesh, state, (self.batch_size,) + x_train.shape[1:],
            inputs.seq_dim)))
        if self._telemetry is not None:
            self._persist_run_snapshot(mesh, x_train, n_params)

        restored = None
        if meta is not None:
            try:
                restored, meta = restore_checkpoint(ck_dir, state)
            except Exception as e:  # config drift: start fresh
                self.error(f'checkpoint restore failed ({e}); '
                           f'starting from scratch')
                meta = None
                if target_stage is not self.stages[0]:
                    # the state above was built with the saved stage's
                    # optimizer — rebuild for a true from-scratch start
                    optimizer = self._stage_optimizer(
                        self.stages[0], run.steps_per_epoch)
                    state = create_train_state(
                        model, optimizer, sample,
                        jax.random.PRNGKey(self.seed), mesh=mesh,
                        with_dropout_rng=True)
        if jax.process_count() > 1:
            # restore SUCCESS must also be unanimous (same hazard
            # _infer_valid votes on): a rank that restored while another
            # starts from scratch trains collectives on divergent
            # params with no error raised
            from jax.experimental import multihost_utils
            have_file = bool(self.params_file) and (
                os.path.exists(self.params_file) or os.path.exists(
                    self.params_file + '.msgpack'))
            votes = multihost_utils.process_allgather(np.array(
                [restored is not None, have_file]))
            restored_flags, file_flags = votes[:, 0], votes[:, 1]
            if restored_flags.any() != restored_flags.all():
                raise RuntimeError(
                    'checkpoint restore succeeded on some hosts only — '
                    'sync the checkpoint folder before resuming')
            if self.params_file and restored is None \
                    and not file_flags.all():
                raise FileNotFoundError(
                    f'params_file {self.params_file!r} must be readable '
                    f'on EVERY host ({int(file_flags.sum())}/'
                    f'{len(file_flags)} have it)')
        if restored is None and self.params_file:
            # pretrained weights seed a FRESH run only; a checkpoint
            # restore (resume) wins over them, like the reference where
            # resume checkpoints override pretrained encoder weights
            from mlcomp_tpu.train.pretrained import apply_pretrained
            state, summary = apply_pretrained(state, self.params_file)
            self.info(f'pretrained {self.params_file}: {summary}')
        if restored is not None:
            from mlcomp_tpu.train.loop import place_state
            state = place_state(restored, mesh)
            run.first_global_epoch = run.global_epoch = \
                int(meta.get('epoch', -1)) + 1
            run.best = self._restored_best(ck_dir)
            self.info(
                f'resumed from checkpoint: stage={meta.get("stage")} '
                f'epoch={meta.get("epoch")} best={run.best}')
        run.state, run.meta = state, meta

    def _persist_run_snapshot(self, mesh, x_train, n_params):
        """The run.snapshot row: the mesh / batch-shape / model context
        the postmortem bundle freezes next to the series (which say
        WHAT happened — this says on what)."""
        from mlcomp_tpu.telemetry import persist_run_snapshot
        try:
            persist_run_snapshot(self.session, self.task.id, {
                'model': self.model_spec.get('name'),
                'model_spec': {k: v for k, v in
                               self.model_spec.items()
                               if isinstance(v, (str, int, float,
                                                 bool))},
                'n_params': int(n_params),
                'mesh': {k: int(v) for k, v in
                         dict(mesh.shape).items()},
                'devices': len(mesh.devices.flat),
                'batch_size': int(self.batch_size),
                'batch_shape': [int(self.batch_size)]
                + [int(d) for d in x_train.shape[1:]],
                'input_dtype': str(x_train.dtype),
                'loss': self.loss_name,
            })
        except Exception:
            pass            # context is best-effort, never fatal

    @staticmethod
    def _restored_best(ck_dir):
        """Best-score tracking seeded from the surviving best
        checkpoint, so a post-resume epoch can't clobber a better
        best.msgpack."""
        best = None
        best_meta = load_meta(ck_dir, 'best')
        if best_meta and best_meta.get('score') is not None:
            best = float(best_meta['score'])
        if jax.process_count() > 1:
            # the seed must be UNANIMOUS: is_best gates collective
            # barriers inside the sharded best-save, so ranks
            # disagreeing on `best` (a host whose best/ folder
            # missed the sync) would split at the barrier and hang
            from jax.experimental import multihost_utils
            seeds = multihost_utils.process_allgather(np.array(
                [best is not None,
                 float('nan') if best is None else float(best)]))
            flags, scores = seeds[:, 0], seeds[:, 1]
            same = flags.all() and (
                np.nanmax(scores) - np.nanmin(scores) < 1e-12) \
                or not flags.any()
            if not same:
                raise RuntimeError(
                    f'best-checkpoint meta differs across hosts '
                    f'({seeds.tolist()}) — sync the checkpoint '
                    f'folder before resuming')
        return best

    # ------------------------------------------------------------ per stage
    def _setup_stage(self, run, inputs, spec) -> _Stage:
        """The stage's optimizer, its train step (wrapped for telemetry)
        and its validation call, by input path."""
        mesh, model, loss_fn = run.mesh, run.model, run.loss_fn
        optimizer = self._stage_optimizer(spec, run.steps_per_epoch)
        # validation takes the rows of one batch and their weights. On
        # the device-data path the valid set is HBM-resident too —
        # per-batch transfer is an index + weight vector, not the images
        if inputs.use_device_data:
            from mlcomp_tpu.train.loop import (
                make_device_eval_step, make_device_train_step,
            )
            train_step = make_device_train_step(
                model, optimizer, loss_fn, mesh=mesh,
                augment=inputs.dev_augment, dequantize=inputs.dequant,
                row_shape=inputs.x_train.shape[1:])
            eval_step = make_device_eval_step(
                model, loss_fn, mesh=mesh, dequantize=inputs.dequant_v,
                row_shape=inputs.x_valid.shape[1:])

            def evaluate(state, take, w_dev):
                idx = jax.device_put(take.astype(np.int32),
                                     batch_sharding(mesh, 1))
                return eval_step(state, inputs.xv_all, inputs.yv_all,
                                 idx, w_dev)
        else:
            train_step = make_train_step(
                model, optimizer, loss_fn, mesh=mesh,
                self_supervised=run.self_supervised)
            eval_step = make_eval_step(
                model, loss_fn, mesh=mesh,
                self_supervised=run.self_supervised)

            def evaluate(state, take, w_dev):
                bx = inputs.x_valid[take]
                by = inputs.y_valid[take] \
                    if inputs.y_valid is not None else None
                x, y = place_batch((bx, by), mesh,
                                   seq_dim=inputs.seq_dim)
                return eval_step(state, x, y, w_dev)
        if self._telemetry is not None:
            self._introspect(mesh, train_step, run.state,
                             *self._abstract_step_args(run, inputs))
            from mlcomp_tpu.train.loop import instrumented_step
            train_step = instrumented_step(
                train_step, self._telemetry,
                attribution=self._attribution,
                tripwire=self._tripwire,
                compile_events=self._compile_events,
                memory=self._memory,
                deviceprof=self._deviceprof)
        return _Stage(name=spec['name'],
                      epochs=int(spec.get('epochs', 1)),
                      optimizer=optimizer, train_step=train_step,
                      evaluate=evaluate)

    # ------------------------------------------------------------ per epoch
    def _epoch(self, run, inputs, stage, epoch):
        """One epoch, its phases in order as spans (DB rows for every
        epoch, host-line annotations in any profiler trace): begin (up
        to the first dispatch) -> steps -> drain (wait for the last
        step, pull its metrics) -> valid -> report (nothing queued on
        the device) -> checkpoint. Per-step phases stay the
        step.phase.* counters."""
        # a static `profile:` trace opens first, so that it holds the
        # whole train.epoch annotation
        profiling = self._maybe_start_profile(run.global_epoch,
                                              run.ck_dir)
        with self._span('train.epoch', epoch=run.global_epoch,
                        stage=stage.name):
            train_agg, train_dt = self._train_epoch(run, inputs, stage,
                                                    epoch)
            valid_agg = self._validate(run, inputs, stage)
            score, is_best, sweep_rung = self._report_epoch(
                run, stage, train_agg, valid_agg, train_dt)
            self._checkpoint(run, stage, epoch, score, is_best,
                             sweep_rung)
        if profiling:
            self._stop_profile(run.global_epoch)
        run.global_epoch += 1
        # chaos seams (mlcomp_tpu/testing/faults.py): the
        # kill-worker-mid-epoch fault dies HERE, after epoch
        # N's checkpoint submit — one module-global check per
        # seam when no faults are armed. gang.rank_exit
        # additionally carries the rank + gang so a `when`
        # filter kills exactly one rank of a multi-host gang
        # (the elastic-recovery acceptance chaos), even though
        # MLCOMP_FAULTS arms every rank's subprocess alike
        from mlcomp_tpu.testing.faults import fault_point
        fault_point('train.epoch', epoch=run.global_epoch,
                    task=self.task.id if self.task else None)
        distr = dict(getattr(self, 'additional_info', None)
                     or {}).get('distr_info') or {}
        if distr:
            fault_point(
                'gang.rank_exit', phase='epoch',
                epoch=run.global_epoch,
                rank=distr.get('process_index'),
                gang=(distr.get('gang') or {}).get('id'),
                task=self.task.id if self.task else None)

    def _feed(self, run, inputs, ep_rng):
        """The epoch's train batches as the arguments of the stage's
        train step after the state, one tuple a step. What is done here
        and not in the iterator is still the epoch's beginning."""
        x_train = inputs.x_train
        steps = run.steps_per_epoch
        if steps * self.batch_size > len(x_train):
            raise ValueError(
                f'dataset has {len(x_train)} train samples — '
                f'fewer than batch_size={self.batch_size}; no '
                f'full batch to train on')
        first_of_run = run.global_epoch == run.first_global_epoch
        if inputs.use_device_data:
            dropped = len(x_train) % self.batch_size
            if dropped and first_of_run:
                self.info(
                    f'dropping {dropped} tail samples '
                    f'(n={len(x_train)} not divisible by '
                    f'batch_size={self.batch_size})')
            perm = ep_rng.permutation(
                len(x_train))[:steps * self.batch_size]
            perm = perm.astype(np.int32).reshape(steps, self.batch_size)
            return self._device_feed(run.mesh, inputs, perm)
        batches = iterate_batches(
            x_train, inputs.y_train, self.batch_size, ep_rng,
            transform=inputs.transform,
            logger=self.info if first_of_run else None)
        placed = iter(prefetch_batches(
            batches, run.mesh, seq_dim=inputs.seq_dim,
            depth=self.prefetch, attribution=self._attribution))
        # the first batches are shuffled, augmented and placed before
        # anything is dispatched
        first = next(placed, None)
        return itertools.chain(() if first is None else (first,), placed)

    def _device_feed(self, mesh, inputs, perm):
        attr, sharding = self._attribution, batch_sharding(mesh, 1)
        for s in range(len(perm)):
            # device-data path attribution: permutation slicing is the
            # data wait, the index device_put is the h2d leg (the batch
            # itself is already HBM-resident)
            if attr is not None:
                attr.begin('data_wait')
            idx_host = perm[s]
            if attr is not None:
                attr.begin('h2d')
            yield inputs.x_all, inputs.y_all, jax.device_put(
                idx_host, sharding)

    def _train_epoch(self, run, inputs, stage, epoch):
        """begin -> steps -> drain: each phase ends where the next
        starts. Returns the epoch's mean train metrics and its
        seconds."""
        with self._span('train.epoch.begin'):
            self.step.start(2, f'epoch {epoch}', epoch)
            ep_rng = np.random.RandomState(self.seed * 1000 + epoch)
            t_ep = time.time()
            feed = self._feed(run, inputs, ep_rng)
            train_metrics = []
        with self._span('train.epoch.steps'):
            for args in feed:
                run.state, metrics = stage.train_step(run.state, *args)
                train_metrics.append(metrics)
        with self._span('train.epoch.drain'):
            # metrics: device→host ONCE per epoch
            train_agg = aggregate_metrics(train_metrics)
            run.images_seen += len(train_metrics) * self.batch_size
            train_dt = time.time() - t_ep
        return train_agg, train_dt

    @_phase('train.epoch.valid')
    def _validate(self, run, inputs, stage):
        """Evaluate EVERY validation sample: tail batches are padded
        (duplicate samples) up to a multiple of the data-parallel
        width, with zero weights on the padding so aggregates stay
        exact."""
        mesh = run.mesh
        dp = max(1, data_parallel_size(mesh))
        valid_metrics, valid_weights = [], []
        n_valid_total = len(inputs.x_valid)
        for start in range(0, n_valid_total, self.eval_batch_size):
            n_real = min(self.eval_batch_size, n_valid_total - start)
            n_padded = -(-n_real // dp) * dp
            take = np.resize(np.arange(start, start + n_real), n_padded)
            w = np.ones(n_padded, np.float32)
            w[n_real:] = 0.0
            w_dev = jax.device_put(w, batch_sharding(mesh, 1))
            valid_metrics.append(stage.evaluate(run.state, take, w_dev))
            valid_weights.append(n_real)
        return aggregate_metrics(valid_metrics, weights=valid_weights)

    @_phase('train.epoch.report')
    def _report_epoch(self, run, stage, train_agg, valid_agg, train_dt):
        """The epoch's host rows (series, gauges, the log line) and
        what they decide: ``(score, is_best, sweep_rung)``."""
        global_epoch = run.global_epoch
        steps_per_epoch = run.steps_per_epoch
        n_train = steps_per_epoch * self.batch_size
        # the model's counters (train/loop.py STEP_COUNTERS):
        # the epoch's mean over its steps, a series each
        counters = {k: train_agg.pop(k) for k in STEP_COUNTERS
                    if k in train_agg}
        for k, v in train_agg.items():
            self._report_series(k, v, global_epoch, 'train', stage.name)
        for k, v in valid_agg.items():
            self._report_series(k, v, global_epoch, 'valid', stage.name)
        self._report_series('images_per_sec', n_train / train_dt,
                            global_epoch, 'train', stage.name)
        if self._telemetry is not None:
            tel = self._telemetry
            for k, v in counters.items():
                tel.series(k, v, step=global_epoch)
            if self._step_flops:
                from mlcomp_tpu.telemetry import mfu as _mfu
                peak = float(self.telemetry_spec.get(
                    'peak_tflops',
                    os.environ.get('MLCOMP_PEAK_TFLOPS', 197)))
                tel.gauge('mfu', _mfu(
                    self._step_flops, steps_per_epoch / train_dt,
                    len(run.mesh.devices.flat), peak))
            from mlcomp_tpu.telemetry import record_device_stats
            record_device_stats(tel)
            if self._comm_probe_ms:
                # measured comm share of the observed step: the wire
                # time of this step's collectives
                # (telemetry/collectives.py probe, once per stage)
                # over the epoch's mean step time — the "is my step
                # communication-bound" series
                step_ms = train_dt * 1e3 / steps_per_epoch
                if step_ms > 0:
                    tel.series(
                        'comm.fraction',
                        min(1.0, self._comm_probe_ms / step_ms),
                        step=global_epoch)
            if self._attribution is not None \
                    and self._attribution.steps:
                # bench's pipeline_efficiency, from inside the real
                # run (per-step step.phase.* series landed already;
                # this is the per-epoch derived gauge)
                self._attribution.emit_epoch(tel, epoch=global_epoch)
            tel.flush()
        if self._profiler is not None:
            self._profiler.poll()
        self.info(
            f'[{stage.name}] epoch {global_epoch}: '
            f'train {train_agg} valid {valid_agg} '
            f'({n_train / train_dt:.0f} samples/s)')

        score = valid_agg.get(self.main_metric,
                              train_agg.get(self.main_metric))
        is_best = score is not None and (
            run.best is None or
            (score < run.best if self.minimize else score > run.best))
        if is_best:
            run.best = score
            self._update_scores(score)
        # ASHA sweep cell (additional_info['sweep'], stamped at
        # submission): report the rung score the supervisor judges on
        # — immediate row + supervisor wakeup, so a losing cell is
        # pruned at the next tick instead of training a whole extra
        # rung
        sweep_rung = self._report_sweep(
            global_epoch, steps_per_epoch, score)
        return score, is_best, sweep_rung

    def _checkpoint(self, run, stage, epoch, score, is_best, sweep_rung):
        """Save by the cadence: pulling the full state to host is the
        dominant per-epoch cost on slow host links — save on best,
        every checkpoint_every-th epoch, and at the stage's final epoch
        (so resume/export always has a fresh `last`). The span is
        absent where nothing is saved."""
        # checkpoint_every: 0 disables saving entirely — for
        # grid-search cells whose artifacts are throwaway (they cannot
        # resume or export: __init__ rejects the consumers). Sweep rung
        # boundaries force a save: promotion is checkpoint-aware — a
        # promoted cell that later dies transiently resumes from its
        # RUNG checkpoint through the ordinary retry path
        # (checkpoint_every: 0 still wins)
        if self.checkpoint_every == 0 or not (
                is_best or self.checkpoint_every <= 1
                or (run.global_epoch + 1) % self.checkpoint_every == 0
                or epoch == stage.epochs - 1 or sweep_rung):
            return
        with self._span('train.epoch.checkpoint'):
            state, ck_dir = run.state, run.ck_dir
            meta_d = {'stage': stage.name, 'stage_epoch': epoch,
                      'epoch': run.global_epoch, 'score': score,
                      'step': int(state.step)}
            from mlcomp_tpu.train.ckpt_shard import (
                build_shard_plan, state_needs_sharded_ckpt,
                write_shard_plan,
            )
            if state_needs_sharded_ckpt(state):
                # sharded format: each process pulls only ITS
                # addressable replica-0 shards (no collective, no
                # full-state buffer on any host) and writes its own
                # fragment files; rank 0 adds the index
                plan = build_shard_plan(state)
                if self._ckpt_writer is not None \
                        and jax.process_count() == 1:
                    # off-thread only single-process: the
                    # multi-process write barriers are collectives and
                    # must stay on the main thread, ordered with the
                    # train step's
                    self._ckpt_writer.submit_job(
                        write_shard_plan, ck_dir, plan, meta_d,
                        best=is_best)
                else:
                    write_shard_plan(ck_dir, plan, meta_d,
                                     best=is_best)
            else:
                # single-process by construction (multi-process always
                # takes the sharded branch above): flat msgpack blob
                # (reference rank-0 write, catalyst.py:298-311)
                host_state = jax.device_get(state)
                if self._ckpt_writer is not None:
                    # serialise+write off-thread: the next epoch's
                    # compute overlaps the disk IO
                    self._ckpt_writer.submit(
                        ck_dir, host_state, meta_d, best=is_best)
                else:
                    save_checkpoint(ck_dir, host_state, meta_d,
                                    best=is_best)

    def _maybe_start_profile(self, global_epoch, ck_dir) -> bool:
        """Start an XLA device trace if this epoch is in the profile
        spec (rank 0 only — each host would trace its own runtime)."""
        if not self.profile or not self._is_main:
            return False
        epochs = self.profile.get('epochs')
        if epochs is None:
            epochs = self.profile.get('epoch', 0)
        if not isinstance(epochs, (list, tuple, set)):
            epochs = [epochs]
        if global_epoch not in {int(e) for e in epochs}:
            return False
        out = self.profile.get('dir') or os.path.join(ck_dir, 'profile')
        try:
            jax.profiler.start_trace(out)
        except Exception as e:  # already tracing / unsupported backend
            self.info(f'profiler: could not start trace ({e})')
            return False
        self._profile_dir = out
        self._profile_open = True
        return True

    def _stop_profile(self, global_epoch):
        self._profile_open = False
        try:
            jax.profiler.stop_trace()
            self.info(f'profiler: epoch {global_epoch} device trace -> '
                      f'{self._profile_dir} (open with xprof/'
                      f'tensorboard)')
        except Exception as e:
            self.info(f'profiler: stop_trace failed ({e})')

    def _predict_valid(self, model, state, mesh, x_valid):
        """Softmax predictions over the validation set, batched and
        dp-padded — shared by the report-img pass and infer_valid (the
        jitted forward is cached so both passes compile it once)."""
        forward = getattr(self, '_eval_forward', None)
        if forward is None:
            import jax.numpy as jnp
            from mlcomp_tpu.train.loop import _apply, _jit_in_mesh

            def softmax(s, x):
                logits = _apply(model, s, x, train=False)[0]
                return jax.nn.softmax(jnp.asarray(logits, jnp.float32))

            self._eval_forward = forward = _jit_in_mesh(softmax, mesh)

        dp = max(1, data_parallel_size(mesh))
        probs = []
        for bx, _ in iterate_batches(x_valid, None, self.eval_batch_size,
                                     drop_last=False):
            n_real = len(bx)
            n_padded = -(-n_real // dp) * dp
            if n_padded != n_real:
                bx = bx[np.resize(np.arange(n_real), n_padded)]
            x, _ = place_batch((bx, None), mesh)
            probs.append(np.asarray(forward(state, x))[:n_real])
        return np.concatenate(probs) if probs else np.empty((0,))

    def _infer_valid(self, model, state, mesh, ck_dir, x_valid, y_valid):
        """Save validation predictions as npy for downstream
        Valid/ensemble stages (reference InferBestCallback,
        contrib/catalyst/callbacks/inference.py:10-50: accumulate
        outputs, save the best epoch's). ``best_only`` (default) loads
        the best checkpoint first so the saved preds are the best
        epoch's, not the last's."""
        from mlcomp_tpu.train.checkpoint import restore_checkpoint
        from mlcomp_tpu.worker.executors.base.equation import PRED_FOLDER

        spec = self.infer_valid
        prefix = spec.get('out_prefix') or self.model_name or 'valid'
        do_best = bool(spec.get('best_only', True))
        if do_best and jax.process_count() > 1:
            # every process must make the SAME reload decision or their
            # params diverge mid-collective; a rank without a local
            # best checkpoint (non-shared fs) forces the final state
            from jax.experimental import multihost_utils
            from mlcomp_tpu.train.checkpoint import checkpoint_exists
            have = checkpoint_exists(ck_dir, 'best') is not None
            do_best = bool(multihost_utils.process_allgather(
                np.array(have)).all())
        if do_best:
            from mlcomp_tpu.train.loop import place_state
            # no gather: the msgpack path only reads target STRUCTURE
            # (host values land below via place_state), and the sharded
            # path restores straight onto the live state's shardings —
            # each host reads only its own devices' slices
            try:
                best_state, _ = restore_checkpoint(
                    ck_dir, state, kind='best')
            except Exception as e:  # stage drift: best saved under a
                best_state = None   # different optimizer structure
                if self._is_main:
                    self.info(f'infer_valid: best checkpoint not '
                              f'loadable ({e}); using final state')
            if jax.process_count() > 1:
                # the USE decision must also be unanimous: a rank whose
                # local restore failed (corrupt file) must not keep the
                # final state while others load best
                from jax.experimental import multihost_utils
                ok = multihost_utils.process_allgather(
                    np.array(best_state is not None)).all()
                if not ok:
                    best_state = None
            if best_state is not None:
                state = place_state(best_state, mesh)
            else:
                do_best = False
        cached = getattr(self, '_final_state_probs', None)
        if not do_best and cached is not None:
            # report-img pass already inferred this exact (final) state
            probs = cached
        else:
            probs = self._predict_valid(model, state, mesh, x_valid)
        if not self._is_main:
            return
        os.makedirs(PRED_FOLDER, exist_ok=True)
        out = os.path.join(PRED_FOLDER, f'{prefix}.npy')
        np.save(out, probs)
        if y_valid is not None:
            np.save(os.path.join(PRED_FOLDER, f'{prefix}_y.npy'),
                    np.asarray(y_valid))
        self.info(f'infer_valid: {len(probs)} predictions -> {out}')

    def _build_report_imgs(self, model, state, mesh, x_valid, y_valid,
                           epoch):
        """UI gallery artifacts from the final state (reference wires
        these as Catalyst callbacks, worker/executors/catalyst/f1.py;
        here one post-train pass over the validation set)."""
        spec = self.report_imgs
        kind = spec.get('type', 'classification')
        probs = self._predict_valid(model, state, mesh, x_valid)
        self._final_state_probs = probs  # reusable by _infer_valid
        if not self._is_main:
            return

        common = dict(
            session=self.session, task=self.task, part='valid',
            plot_count=int(spec.get('plot_count', 64)))
        if kind == 'segmentation':
            from mlcomp_tpu.worker.reports import SegmentationReportBuilder
            builder = SegmentationReportBuilder(**common)
            n = builder.build(x_valid, y_valid, probs.argmax(-1),
                              epoch=epoch)
        else:
            from mlcomp_tpu.worker.reports import (
                ClassificationReportBuilder,
            )
            builder = ClassificationReportBuilder(
                class_names=spec.get('class_names'), **common)
            n = builder.build(x_valid, y_valid, probs, epoch=epoch)
        self.info(f'report imgs: {n} {kind} rows for epoch {epoch}')

    def _export_model(self, ck_dir, best_score, input_shape=None,
                      input_dtype=None):
        """Write the deployable export for the model registry — the
        TPU-native analogue of the reference's post-train torch.jit trace
        (catalyst.py:372-374). Best checkpoint wins; falls back to last.
        ``input_shape`` (per-example, no batch dim) + ``input_dtype``
        make the export self-describing enough for the serving process
        to warm up its XLA compile before the first request — and to
        feed INTEGER inputs (LM tokens) as integers."""
        from mlcomp_tpu.train.checkpoint import checkpoint_exists
        from mlcomp_tpu.train.export import export_from_checkpoint
        src = checkpoint_exists(ck_dir, 'best') \
            or checkpoint_exists(ck_dir, 'last')
        if not src:
            return
        out = os.path.join(self._model_folder(), self.model_name)
        meta = {'score': best_score}
        if input_shape:
            meta['input_shape'] = list(input_shape)
        if input_dtype:
            meta['input_dtype'] = str(input_dtype)
        try:
            export_from_checkpoint(src, self.model_spec, out, meta=meta)
        except FileNotFoundError as e:
            # sharded checkpoint on a non-shared fs: rank 0 holds only
            # its own fragment files until FileSync ships the rest —
            # the TRAINING succeeded, so defer the export (a ModelAdd
            # task after sync produces it) instead of failing the task
            self.info(f'WARNING: export deferred — {e}')
            return
        self.info(f'exported model {self.model_name!r} -> {out}.msgpack')

    def _model_folder(self):
        if self.dag is not None and self.session is not None:
            from mlcomp_tpu import MODEL_FOLDER
            from mlcomp_tpu.db.providers import ProjectProvider
            project = ProjectProvider(self.session).by_id(self.dag.project)
            if project is not None:
                return os.path.join(MODEL_FOLDER, project.name)
        return 'models'


__all__ = ['JaxTrain']
