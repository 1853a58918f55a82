"""One run of one training cell.

Set-up builds the program's own train step and state
(``repro.launch.train.setup_training``, as ``train.main`` builds them),
puts the benchmark's seeded weights into that state, and drives the same
object through its first three steps through the window's own feed (the
program's ``Prefetcher`` and ``place_batch``), reading the losses, the
first gradient and the change after three steps from its state.  The
window then drives that object for ``--seconds``, keeping the traffic's
``ahead_steps`` steps dispatched ahead of the one it waits for, so that
the chip stays fed while the host stands still; a step's time is the
interval between its outputs and the previous step's being ready.  When
the time is up nothing more is sent, and the window closes once every
step sent has finished: all of that work counts, over all of that time.  Once the window
has closed and the peak memory is read, the program's state is freed and
the plain reference follows the same three steps; ``correct`` compares
the two.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import shutil
import statistics
import tempfile
import time

import numpy as np

from harness import compare, kernels, reference, trace, weights

FIRST_STEPS = 3


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def devices_for(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     f"the benchmark never runs on another device")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def program_config(sizes: dict, traffic: dict):
    """The program's ArchConfig with every field that the sizes file, and
    then the traffic file's ``program``, names set to the file's value."""
    from repro.configs import get_arch

    cfg = get_arch(sizes["arch"])
    fields = {f.name for f in dataclasses.fields(cfg)}
    unknown = set(traffic["program"]) - fields
    if unknown:
        raise KeyError(f"the program's configuration has no {sorted(unknown)}")
    over = {k: v for k, v in sizes.items() if k in fields}
    return dataclasses.replace(cfg, **{**over, **traffic["program"]})


class Ring:
    """A dataset for the program's ``Prefetcher``: step s gets batch
    s mod len(ring), so the first len(ring) steps all see distinct rows."""

    def __init__(self, ring: list):
        self.ring = ring

    def batch_at(self, step: int) -> dict:
        return self.ring[step % len(self.ring)]


def _paths(tree):
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path) for path, _ in flat]
    return names, [a for _, a in flat], treedef


def seed_weights(state: dict, layout: dict, seed: int, lead: int) -> dict:
    """Put the benchmark's weights into the program's state, in the
    program's own shardings; the program's own initial values are
    freed."""
    import jax
    import jax.numpy as jnp

    names, leaves, treedef = _paths(state["params"])
    if sorted(names) != sorted(layout):
        raise ValueError("the program's parameters differ from the "
                         f"configuration's layout: {sorted(names)[:4]}... "
                         f"vs {sorted(layout)[:4]}...")
    for n, a in zip(names, leaves):
        shape = ((lead,) if lead else ()) + tuple(layout[n][0])
        if a.shape != shape or a.dtype != jnp.dtype(layout[n][1]):
            raise ValueError(f"{n}: program {a.shape} {a.dtype}, layout "
                             f"{shape} {layout[n][1]}")
    made = weights.make_params(layout, seed, lead,
                               {n: a.sharding for n, a in zip(names, leaves)})
    for key in ("params", "prev_params"):
        if key in state:
            for a in jax.tree.leaves(state[key]):
                a.delete()
            vals = [made[n] if key == "params" else jnp.copy(made[n])
                    for n in names]
            state[key] = jax.tree_util.tree_unflatten(treedef, vals)
    return state


def params_by_path(state: dict) -> dict:
    names, leaves, _ = _paths(state["params"])
    return dict(zip(names, leaves))


@contextlib.contextmanager
def _span(name: str, on: bool):
    if on:
        import jax

        with jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + name):
            yield
    else:
        yield


def _p95(xs):
    return statistics.quantiles(xs, n=20, method="inclusive")[18]


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    trace: trace.Trace
    chips: int
    peak: dict
    model_flops_per_step: float
    mosaic: dict                    # kernel name -> call shapes
    host: dict                      # span -> [seconds per window step]


@dataclasses.dataclass
class Job:
    """The program's train step and state for one cell, as set-up built
    them, with the feed the window uses."""

    cell: object
    devices: list
    mesh: object
    state: dict
    step: object
    rules: object
    initial: dict          # state entries other than the parameters


def build(cell, devices, seed: int, wrap_step=None) -> Job:
    """``setup_training`` as ``train.main`` calls it, then the benchmark's
    weights for ``seed`` in place of the program's."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import setup_training

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sizes, tr = cell.sizes, cell.traffic
    mesh = make_local_mesh(data=len(devices), devices=devices)
    state, step, meta = setup_training(
        program_config(sizes, tr), mesh, strategy_name=tr["strategy"],
        n_learners=tr["learners"], optimizer_name=tr["optimizer"],
        seed=seed, kernel_impl=sizes.get("kernel_impl", "jax"),
        lr_schedule=cell.schedule.for_program(tr["lr"]),
        microbatches=tr["microbatches"])
    if wrap_step is not None:
        step = wrap_step(step)
    initial = {k: jax.tree.map(lambda a: a.copy(), v)
               for k, v in state.items()
               if k not in ("params", "prev_params")}
    job = Job(cell, devices, mesh, state, step, meta["rules"], initial)
    job.state = seed_weights(state, cell.model.layout(sizes), seed,
                             reference.lead(cell))
    return job


def reseed(job: Job, seed: int) -> None:
    """Start the same program object again from ``seed``'s weights."""
    import jax

    state = {k: jax.tree.map(lambda a: a.copy(), v)
             for k, v in job.initial.items()}
    state["params"] = job.state["params"]
    if "prev_params" in job.state:
        state["prev_params"] = job.state["prev_params"]
    for k, v in job.state.items():
        if k not in ("params", "prev_params"):
            for a in jax.tree.leaves(v):
                a.delete()
    job.state = seed_weights(state, job.cell.model.layout(job.cell.sizes),
                             seed, reference.lead(job.cell))


def first_steps(job: Job, seed: int, pf) -> dict:
    """Drive the job through its first steps through the window's own
    feed; the readings ``correct`` compares."""
    import jax
    import jax.numpy as jnp

    from repro.launch.train import place_batch

    grad_fn, change_fn = reference.readings_fns(job.cell, FIRST_STEPS)
    key = weights.key_of(seed)
    got = {"losses": []}
    with jax.set_mesh(job.mesh):
        for k in range(FIRST_STEPS):
            batch = place_batch(pf.next(), job.rules)
            job.state, m = job.step(job.state, batch)
            got["losses"].append(float(m["loss"]))
            if k == 0:
                got["grad"] = reference.to_host(grad_fn(
                    key, params_by_path(job.state),
                    jnp.float32(reference.lr_at(job.cell, 0))))
        got["change"] = reference.to_host(change_fn(
            key, params_by_path(job.state)))
    return got


def free(job: Job) -> None:
    import jax

    for a in jax.tree.leaves((job.state, job.initial)):
        a.delete()
    job.state = job.initial = None


def run(cell, seed: int, seconds: float, traced: bool, t0: float, *,
        require_tpu: bool = True, wrap_step=None, peaks: dict = None,
        log=print) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import Prefetcher
    from repro.launch.train import place_batch

    devices = devices_for(cell.chips, require_tpu)
    peak = _peak(peaks, devices[0].device_kind) if peaks else {}
    sizes, tr, model = cell.sizes, cell.traffic, cell.model
    t_dev = time.perf_counter()
    job = build(cell, devices, seed, wrap_step)
    t_build = time.perf_counter()
    ring = cell.batches(seed)
    pf = Prefetcher(Ring(ring))
    t_ring = time.perf_counter()
    got = first_steps(job, seed, pf)
    log(f"setup: to devices {t_dev - t0:.3f} s, build {t_build - t_dev:.3f}"
        f" s, batches {t_ring - t_build:.3f} s, first steps "
        f"{time.perf_counter() - t_ring:.3f} s")
    step, rules, state = job.step, job.rules, job.state

    with jax.set_mesh(job.mesh):
        cache0 = _cache_size(step)
        host = {n: [] for n in ("fetch", "place", "dispatch", "block")}
        ahead = int(tr["ahead_steps"])
        pending = collections.deque()
        ready, losses = [], []
        tmp = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        if traced:
            jax.profiler.start_trace(tmp)
        t_win = time.perf_counter()
        setup_s = t_win - t0
        with _span("window", traced):
            while True:
                ts = time.perf_counter()
                with _span("step", traced):
                    with _span("fetch", traced):
                        batch_np = pf.next()
                    t1 = time.perf_counter()
                    with _span("place", traced):
                        batch = place_batch(batch_np, rules)
                    t2 = time.perf_counter()
                    with _span("dispatch", traced):
                        state, m = step(state, batch)
                    t3 = time.perf_counter()
                    pending.append(m)
                    losses.append(m["loss"])
                    if len(pending) > ahead:
                        with _span("block", traced):
                            jax.block_until_ready(pending.popleft())
                        ready.append(time.perf_counter())
                te = time.perf_counter()
                for n, a, b in (("fetch", ts, t1), ("place", t1, t2),
                                ("dispatch", t2, t3), ("block", t3, te)):
                    host[n].append(b - a)
                if te - t_win >= seconds:
                    break
            # the window closes once every step sent has finished
            with _span("drain", traced):
                while pending:
                    jax.block_until_ready(pending.popleft())
                    ready.append(time.perf_counter())
                jax.block_until_ready(state)
        te = time.perf_counter()
        times = list(np.diff([t_win] + ready))
        window_s = te - t_win
        if traced:
            jax.profiler.stop_trace()
        compiles = _cache_size(step) - cache0
        mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)
        hlo = None
        if traced:
            avals = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding),
                (state, batch))
            hlo = step.lower(*avals).compile().as_text()
        window_losses = np.asarray(jax.device_get(jnp.stack(losses)))
    pf.close()
    job.state = state
    free(job)
    for a in jax.tree.leaves((batch, m)):
        a.delete()
    del state, batch, m, losses

    t_ref = time.perf_counter()
    want = reference.run(cell, seed, ring[:FIRST_STEPS], devices)
    nums = compare.numbers(got, want)
    checks = compare.checks(nums, cell.limits)
    log(f"window: {len(times)} steps in {window_s:.3f} s, {ahead} ahead; "
        f"mean ms fetch {_ms(host['fetch'])} place {_ms(host['place'])} "
        f"dispatch {_ms(host['dispatch'])} block {_ms(host['block'])}; "
        f"step ms median {_ms([statistics.median(times)])} max "
        f"{_ms([max(times)])}")
    log(f"reference: {time.perf_counter() - t_ref:.3f} s; losses program "
        f"{got['losses']} reference {want['losses']}; compiles in window "
        f"{compiles}")

    n = len(times)
    failed = int(np.sum(~np.isfinite(window_losses)))
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    result = {"correct": bool(compare.passed(checks) and failed == 0),
              "attempted": n, "failed": failed}
    if traced:
        tr_ = trace.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        busy = [trace.busy_ns(evs, tr_.window) * 1e-9
                for evs in tr_.ops.values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = tr_.window_s
        ctx = Context(
            trace=tr_, chips=len(devices),
            peak=peak,
            model_flops_per_step=model.model_flops_per_step(sizes, tr),
            mosaic=kernels.mosaic_calls(hlo),
            host=host)
        metrics = {}
        for m_ in cell.per_layer():
            v = cell.metric_reader(m_["name"]).read(ctx)
            if v is not None:
                metrics[m_["name"]] = {"value": v, "unit": m_["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = trace.breakdown(tr_)
    else:
        audio = model.audio_seconds_per_step(sizes, tr)
        values = {
            "audio_s_per_s_per_chip": n * audio / window_s / len(devices),
            "step_ms_p95": 1e3 * _p95(times) if n >= 2 else math.nan,
            "peak_hbm_gib": mem / 2 ** 30,
            "setup_s": setup_s,
        }
        result["metrics"] = {m_["name"]: {"value": values[m_["name"]],
                                          "unit": m_["unit"]}
                             for m_ in cell.end_to_end()}
        result["device"] = device
    result["checks"] = checks
    return result


def _ms(xs) -> str:
    return f"{1e3 * sum(xs) / max(len(xs), 1):.3f}"


def _cache_size(step) -> int:
    try:
        return int(step._cache_size())
    except Exception:
        return 0


def _peak(peaks: dict, kind: str) -> dict:
    if kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in the peaks "
                       f"table")
    return peaks["devices"][kind]
