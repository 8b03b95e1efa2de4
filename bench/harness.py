"""One run of one cell: set up, measure a window, check, report.

``run.py`` parses the command line and calls :func:`run_cell`.  The order
of a run:

1. find the cell's files, the layer modules of its configuration's kinds
   among them; fail before any work unless JAX's devices are the chips the
   cell asks for, of a kind ``bench/peaks.json`` knows;
2. turn on the persistent compilation cache
   (``repro.serving.configure_persistent_cache``: ``$JAX_COMPILATION_CACHE_DIR``
   or ``.jax_cache`` in the checkout);
3. build the program's model at the configuration's widths and draw its
   weights from ``--seed`` (``bench/weights.py``);
4. hand both to the cell's entry (``bench/entries/<entry>.py``), which
   builds the engine, warms every program and shape its traffic uses, and
   measures its window; ``setup_s`` runs from process start to the window;
5. read the peak device memory, free the program's state, and hold a
   sample of what the window produced to the float32 reference
   (``bench/check.py``);
6. print the numbers compared, each beside its limit, on standard error,
   and the result as the last line of standard output.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any

from bench import check, loader, tracing

#: where traced runs write their profile (inside the checkout, one
#: directory a run, removed once read)
OUT_DIR = os.path.join(loader.ROOT, ".bench_out")


class NoChip(SystemExit):
    pass


@dataclasses.dataclass
class Ctx:
    """What an entry gets: the cell's files, the model, and the window."""

    cell: str
    workload: dict
    config: dict            # the configuration as run (smoke sizes in rehearsal)
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    seq_divisor: int
    devices: list
    dlm: Any = None
    params: Any = None
    _window: dict = dataclasses.field(default_factory=dict)

    def engine_config(self):
        """The cell's EngineConfig (buckets divided down in rehearsal)."""
        from repro.serving import EngineConfig

        e = self.workload["engine"]
        return EngineConfig(
            solver=e["solver"], nfe=e["nfe"], k=e["k"], per_sample=e["per_sample"],
            batch_buckets=tuple(e["batch_buckets"]),
            seq_buckets=tuple(s // self.seq_divisor for s in e["seq_buckets"]),
            warmup="grid", compile_cache=True,
        )

    def policy(self):
        from repro.serving import SchedulerPolicy

        return SchedulerPolicy(**self.workload["engine"]["policy"])

    @contextlib.contextmanager
    def window(self):
        """Wraps the measured window: the profiler (traced runs), the
        ``bench.window`` span, and the compiles counted inside it."""
        import jax

        w = self._window
        w["compiles_before"] = compiles()
        stack = contextlib.ExitStack()
        with stack:
            trace_dir = None
            if self.trace:
                os.makedirs(OUT_DIR, exist_ok=True)
                trace_dir = tempfile.mkdtemp(prefix="trace-", dir=OUT_DIR)
                stack.enter_context(tracing.capture(trace_dir))
            stack.enter_context(jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN))
            w["start"] = time.perf_counter()
            yield w
            w["end"] = w.get("end", time.perf_counter())
        w["compiles_after"] = compiles()
        w["trace_dir"] = trace_dir


# ---------------------------------------------------------------------------
# compiles, counted from JAX's own monitoring events
# ---------------------------------------------------------------------------

# process-wide, as JAX's monitoring listeners are: registered once
_COMPILES = {"backend": 0, "disk": 0}
_LISTENING = []


def _listen() -> None:
    from jax import monitoring

    if _LISTENING:
        return
    _LISTENING.append(True)

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES["backend"] += 1

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILES["disk"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def compiles() -> dict:
    return dict(_COMPILES)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def check_devices(chips: int, rehearse: bool):
    """The devices the cell runs on, or NoChip: a TPU of a known kind, at
    least ``chips`` of them (rehearsal: the CPU)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if rehearse:
        if dev.platform != "cpu":
            raise NoChip(f"rehearsal runs on the CPU, found {dev.platform}")
        return devices[:chips]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU found (platform {dev.platform}); nothing measured")
    loader.peaks(dev.device_kind)   # an unknown kind is an error
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def peak_memory(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def measure(cell: str, seed: int, seconds: float, trace: bool,
            rehearse: bool = False, t0: float | None = None) -> dict:
    """Steps 1-5 up to the check: returns the run's record, with the
    weights (``params``), peak memory and trace summary."""
    t0 = time.perf_counter() if t0 is None else t0
    w = loader.workload(cell)
    cfg_file = loader.config(w["config"])
    spec = loader.traffic(w["traffic"])
    divisor = int(w.get("rehearse", {}).get("seq_divisor", 1)) if rehearse else 1
    run_cfg = dict(cfg_file, **cfg_file["smoke"]) if rehearse else dict(cfg_file)
    loader.layers(run_cfg)      # a kind with no module stops the run here

    devices = check_devices(int(w["chips"]), rehearse)
    _listen()
    from repro.serving import configure_persistent_cache

    configure_persistent_cache()

    ctx = Ctx(cell, w, run_cfg, spec, seed, seconds, trace, rehearse, divisor, devices)
    import jax

    from bench import weights
    from repro.models import build_model
    from repro.models.diffusion import DiffusionLM

    pcfg = weights.program_config(cfg_file, rehearse)
    ctx.dlm = DiffusionLM(build_model(pcfg))
    ctx.params = weights.make_weights(
        ctx.dlm.init_abstract(), seed, run_cfg["denoiser"]["eps_head_gain"]
    )
    jax.block_until_ready(ctx.params)

    record = loader.entry(w["entry"]).run(ctx)
    win = ctx._window
    record.update(
        cell=cell, seed=seed, chips=len(devices), config=run_cfg, workload=w,
        devices=devices,
        peaks=None if rehearse else loader.peaks(devices[0].device_kind),
        setup_s=win["start"] - t0, window_s=win["end"] - win["start"],
        compiles_in_window={
            k: win["compiles_after"][k] - win["compiles_before"][k]
            for k in win["compiles_before"]
        },
        memory_peak_bytes=peak_memory(devices),
    )
    record["trace"] = None
    if trace:
        record["trace"] = tracing.reduce(tracing.load(win["trace_dir"]))
        shutil.rmtree(win["trace_dir"], ignore_errors=True)
    record["params"] = ctx.params
    ctx.dlm = ctx.params = None
    gc.collect()
    return record


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, t0: float | None = None) -> dict:
    """One run; returns the result line (a dict).  Raises NoChip before
    any work when the devices are not the cell's."""
    record = measure(cell, seed, seconds, trace, rehearse, t0)
    ends, layers = loader.cell_metrics(loader.benchmark(), cell)
    params = record.pop("params")
    w, run_cfg, devices = record["workload"], record["config"], record["devices"]
    summary, memory = record["trace"], record["memory_peak_bytes"]
    t_check = time.perf_counter()
    compared = check.run(record, params, run_cfg, w["check"], seed, rehearse)
    check_s = time.perf_counter() - t_check
    del params
    correct = check.correct(compared)

    wanted = layers if trace else ends
    metrics = {}
    for m in wanted:
        value = loader.metric(m["name"]).read(record, summary)
        if value is None:
            continue
        if not math.isfinite(value):
            raise RuntimeError(f"metric {m['name']} read {value}")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = devices[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices), "memory_peak_bytes": memory,
    }
    if trace:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    line = {
        "correct": correct,
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace:
        line["breakdown"] = {
            "device_ops": [list(x) for x in summary["device_ops"]],
            "idle_gaps": [list(x) for x in summary["idle_gaps"]],
        }
    line["compared"] = compared
    print(
        f"compiles in window: {record['compiles_in_window']}; "
        f"setup_s={record['setup_s']!r} window_s={record['window_s']!r} "
        f"attempted={record['attempted']} failed={record['failed']} "
        f"check_s={check_s!r}",
        file=sys.stderr, flush=True,
    )
    if trace:
        print(f"traced kernels: {summary['kernels']}", file=sys.stderr, flush=True)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return line

