"""Run one cell of the benchmark once, on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``:
the deployment and the index it is served by) and a traffic mix
(``bench/traffic/<name>.json``). Set-up makes the corpus and the query pool on
the device from the seed, builds the index through ``repro.vdms.VDMSInstance``
and warms the one shape the traffic uses; then one window of ``--seconds``
runs with no compilation in it. After the window the answers are compared
with the plain reference (``bench/reference.py``).

A configuration that states ``"shards": N`` (1 where it states none) is served
on N chips by ``repro.vdms.ShardedVDMS`` under ``shard_map``; its cell asks for
N chips. Set-up makes its corpus across those chips a chunk at a time and
copies each chunk to the host for the program, so that the chips hold none of
it when the program builds; the check makes it again there after the window.
The device record and the trace cover the cell's chips (``bench/trace.py``):
``memory_peak_bytes`` is the fullest chip's peak, and
``memory_data_peak_bytes_per_device`` each chip's peak before the program
builds, the most of it that can be the harness's data.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` traces the
window and reports its per-layer metrics, each read by
``bench/metrics/<name>.py`` (or, for a name ``<base>.<split>``, by
``<base>.py``). The last line of standard output is one JSON object; the
numbers compared are the last lines of standard error. Without a TPU, or
without the program beside the benchmark, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
if str(BENCH.parent) not in sys.path:
    sys.path.insert(0, str(BENCH.parent))

CHECK_SAMPLE = 2048  # pool queries searched through the build a build cell checks
TRACE_SECONDS = 6.0  # a --trace 1 run traces the window's first requests, this long
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the files a cell is made of
# ---------------------------------------------------------------------------
def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str, spec: dict) -> tuple[dict, dict, dict]:
    """(cell entry, configuration, traffic mix) of the cell ``name``."""
    from bench import traffic

    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: {sorted(cells)}")
    cell = cells[name]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    mix = traffic.validate(json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text()))
    return cell, validate_cell(cell, config, mix), mix


def shards_of(config: dict) -> int:
    """The chips a configuration is served on: its ``shards``, 1 where it states none."""
    return int(config.get("shards", 1))


def validate_cell(cell: dict, config: dict, mix: dict) -> dict:
    """Refuse a cell whose chips are not its configuration's shards, or that
    the harness cannot check; returns the configuration."""
    shards = shards_of(config)
    if shards < 1 or int(cell["chips"]) != shards:
        raise ValueError(f"cell {cell['name']!r} asks for {cell['chips']} chips; its configuration "
                         f"{config['name']!r} is served on {shards} shards")
    if shards > 1 and mix["op"] == "build":
        raise ValueError(f"cell {cell['name']!r}: a build cell cannot run on {shards} shards, "
                         "because the build check's k-means reference holds the corpus on one device")
    return config


def cell_metrics(spec: dict, cell: str, per_layer: bool) -> list:
    """The metrics a cell reports: those listing it, and those that list no
    cells (per-layer ones among them only where the cell reports what they move)."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if not per_layer:
        return e2e
    moved = {m["name"] for m in e2e}
    return [
        m for m in spec["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]


def reader(name: str):
    """The ``read(ctx)`` function of a metric, found by its name."""
    for stem in (name, name.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(f"bench_metric_{stem}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read
    raise FileNotFoundError(f"no reader for metric {name!r} under {BENCH / 'metrics'}")


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
class Program:
    """The program's static engine under one index configuration, on ``shards`` chips.

    What the harness relies on, and a change to which changes the benchmark:

    * one shard: ``VDMSInstance(dataset, index_config, seed=seed)``, whose
      ``search(queries, topk)`` returns the ids (queries, topk);
    * N shards: ``ShardedVDMS(dataset, index_config, n_shards=N,
      dispatch="shard_map", seed=seed)`` over the first N devices, whose
      ``search(queries, topk, mode="wall")`` returns ``(ids, elapsed)``, of
      which ``ShardedSearcher`` keeps the ids.
    """

    def __init__(self, index_config: dict, shards: int = 1):
        self.index_config, self.shards = dict(index_config), int(shards)

    def build(self, dataset, seed: int):
        from repro.vdms import ShardedVDMS, VDMSInstance

        if self.shards == 1:
            return VDMSInstance(dataset, self.index_config, seed=seed)
        return ShardedSearcher(ShardedVDMS(dataset, self.index_config, n_shards=self.shards,
                                           dispatch="shard_map", seed=seed))

    @staticmethod
    def clusters(built):
        """(centroids, gids) of a build's index: what its k-means made, with
        the corpus row in each slot of each segment."""
        arrays = built.bundle.arrays
        return arrays["centroids"], arrays["gids"]


class ShardedSearcher:
    """A ``ShardedVDMS`` under the searcher contract of the traffic: ids only."""

    def __init__(self, inner):
        self.inner = inner

    def search(self, queries, topk: int) -> np.ndarray:
        ids, _ = self.inner.search(queries, topk, mode="wall")
        return ids


def instrument() -> None:
    """Host spans around the program's build and dispatch steps, from here;
    a step the program no longer has by that name goes without."""
    from repro.vdms import engine

    from bench.traffic import span

    def wrap(name, fn):
        def traced(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return traced

    for attr, name in (("stack_sealed", "stack_sealed"), ("build_index", "build_index"),
                       ("_pipeline", "dispatch")):
        fn = getattr(engine, attr, None)
        if fn is not None and not getattr(fn, "_bench_span", False):
            traced = wrap(name, fn)
            traced._bench_span = True
            setattr(engine, attr, traced)


def dataset_of(corpus_host, pool_host, k: int):
    from repro.vdms import VectorDataset

    # the benchmark's reference takes the place of a stored ground truth
    return VectorDataset(name="glove_like", data=corpus_host, queries=pool_host,
                         ground_truth=np.empty((0, k), np.int32), k=k)


class Tracer:
    """A ``jax.profiler`` trace of the window's first ``seconds``: it stops
    after the first request that returns past them (span ``traced``)."""

    def __init__(self, seconds: float):
        import jax
        from jax.profiler import TraceAnnotation

        self.seconds, self.dir = seconds, tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the host spans are the benchmark's own
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.span = TraceAnnotation("bench/traced")
        self.span.__enter__()

    def on_done(self, elapsed: float) -> None:
        if self.span is not None and elapsed >= self.seconds:
            self._stop()

    def _stop(self) -> None:
        import jax

        self.span.__exit__(None, None, None)
        self.span = None
        jax.profiler.stop_trace()

    def finish(self, chips: int):
        from bench import trace as trace_mod

        if self.span is not None:
            self._stop()
        try:
            return trace_mod.load(self.dir, chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def make_data(config: dict, seed: int):
    """(corpus, pool) of a configuration on the device, from the seed: on one
    device, or across the configuration's shards (``bench/corpus.py``)."""
    from bench import corpus

    shape, shards = config["shape"], shards_of(config)
    n, dim, n_pool = int(shape["n"]), int(shape["dim"]), int(shape["queries"])
    if shards == 1:
        return corpus.make_corpus(seed, n, n_pool, dim)
    return corpus.make_corpus_sharded(seed, n, n_pool, dim, corpus.shard_mesh(shards))


def host_data(config: dict, seed: int):
    """Set-up's data: (corpus, pool, corpus on the host, pool on the host),
    the first two on the device or None. On one device both stay there: the
    corpus for the check, the pool while set-up holds it. Across shards the
    corpus is made and copied to the host a chunk at a time, and the devices
    keep none of it: the check makes it again (``make_data``)."""
    from bench import corpus

    shape, shards = config["shape"], shards_of(config)
    if shards == 1:
        rows, pool = make_data(config, seed)
        return rows, pool, np.asarray(rows), np.asarray(pool)
    rows_host, pool_host = corpus.host_corpus_sharded(seed, int(shape["n"]), int(shape["queries"]),
                                                      int(shape["dim"]), corpus.shard_mesh(shards))
    return None, None, rows_host, pool_host


def peak_bytes(chips: int) -> list:
    """``peak_bytes_in_use`` of each of the first ``chips`` devices so far."""
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()[:chips]]


def set_up(config: dict, mix: dict, seed: int, system=None) -> types.SimpleNamespace:
    """Corpus and pool from the seed, the index built, the traffic's shape warm."""
    from bench import traffic

    k, shards = int(config["shape"]["k"]), shards_of(config)
    system = Program(config["index"], shards) if system is None else system
    instrument()
    corpus, pool, corpus_host, pool_host = host_data(config, seed)  # pool: on one device, freed on return
    data_peaks = peak_bytes(shards)  # what of each chip's peak the harness's data may be
    dataset = dataset_of(corpus_host, pool_host, k)
    searcher = system.build(dataset, seed=0)
    warm_clusters = None
    if mix["op"] == "build":
        warm_clusters = system.clusters(searcher)
        searcher = None  # that was the warm build; the window makes its own
    traffic.warm(mix, system=system, searcher=searcher, dataset=dataset, pool=pool_host)
    return types.SimpleNamespace(corpus=corpus, corpus_host=corpus_host, pool=pool_host,
                                 dataset=dataset, system=system, searcher=searcher, k=k,
                                 warm_clusters=warm_clusters, data_peaks=data_peaks)


def answered(stage, mix: dict, log, seed: int) -> types.SimpleNamespace:
    """What the check compares: the pool rows and their answer ids, from
    every answer of a search window; for builds, a sample of the pool drawn
    from the seed searched through one of the window's builds, itself drawn
    from the seed, with that build's clusters and a fingerprint of every
    build's centroids (the warm build's first)."""
    if mix["op"] != "build":
        return types.SimpleNamespace(rows=np.concatenate(log.rows),
                                     answers=np.concatenate(log.answers)[:, : stage.k])
    rng = np.random.default_rng([seed, 1])
    n_pool = stage.pool.shape[0]
    rows = rng.choice(n_pool, size=min(CHECK_SAMPLE, n_pool), replace=False)
    prints = [hashlib.sha256(np.asarray(c[0]).tobytes()).hexdigest()
              for c in [stage.warm_clusters, *log.clusters] if c is not None]
    return types.SimpleNamespace(rows=rows, answers=log.kept.search(stage.pool[rows], stage.k),
                                 kept=stage.system.clusters(log.kept), prints=prints)


def check(stage, config: dict, seed: int, ev) -> dict:
    """The numbers compared, from ``answered``'s evidence."""
    from bench import reference

    index = config["index"]
    corpus = stage.corpus if stage.corpus is not None else make_data(config, seed)[0]
    tail = reference.sealed_rows(stage.corpus_host.shape[0], int(index["segment_max_size"]),
                                 float(index["seal_proportion"]))
    numbers = reference.compare(ev.answers, stage.pool, ev.rows, stage.corpus_host, corpus,
                                scoring=config["scoring"], exact=bool(config["exact"]), raw_from=tail)
    if hasattr(ev, "kept"):
        numbers["repeated_builds"] = len(ev.prints) - len(set(ev.prints))
        numbers["kmeans_gap"] = float("inf") if ev.kept is None else (
            reference.lloyd_objective(stage.corpus, int(index["segment_max_size"]),
                                      int(index["nlist"]), int(index["kmeans_iters"]), seed)
            - reference.objective(stage.corpus, *ev.kept))
    return numbers


def run_cell(cell: dict, config: dict, mix: dict, *, seed: int, seconds: float, trace: bool,
             metrics: list, system=None) -> dict:
    """Set up, drive one window, read the metrics and check the answers.
    ``system`` replaces the program (controls and planted faults do)."""
    import jax
    from jax._src import monitoring

    from bench import reference, traffic
    from bench import trace as trace_mod

    compiles = []

    def on_event(event, secs, **kw):
        if event == COMPILE_EVENT:
            compiles.append(time.perf_counter())

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        stage = set_up(config, mix, seed, system)
        setup_s = time.perf_counter() - T_START

        tracer = Tracer(TRACE_SECONDS) if trace else None
        w0 = time.perf_counter()
        log = traffic.drive(mix, system=stage.system, searcher=stage.searcher,
                            dataset=stage.dataset, pool=stage.pool, seconds=seconds, seed=seed,
                            on_done=tracer.on_done if trace else (lambda elapsed: None))
        w1 = time.perf_counter()
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    in_window = sum(w0 <= t <= w1 for t in compiles)
    chips = int(cell["chips"])
    tr = tracer.finish(chips) if trace else None

    peaks, dev = peak_bytes(chips), jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count(),
              "memory_peak_bytes": None if None in peaks else max(peaks),  # the fullest chip
              "memory_peak_bytes_per_device": peaks,
              "memory_data_peak_bytes_per_device": stage.data_peaks}
    ctx = types.SimpleNamespace(cell=cell, config=config, mix=mix, log=log, trace=tr,
                                setup_s=setup_s, searcher=stage.searcher, pool=stage.pool,
                                device_kind=dev.device_kind, say=say)
    values = {}
    for m in metrics:
        v = reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    breakdown = None
    if tr is not None:
        a, b = tr.window()
        device["busy_s"] = tr.busy.covered(a, b)  # the mean over the cell's chips
        device["window_s"] = b - a
        device["busy_s_per_device"] = [t.covered(a, b) for t in tr.busy_per_device]
        breakdown = {"device_ops": trace_mod.top_ops(tr, a, b),
                     "idle_gaps": trace_mod.idle_by_activity(tr, a, b)}
        calls = [s for s in tr.spans if s[0] in ("search_call", "build")]
        if calls:  # a stalled request: on the device, or on the host?
            name, c0, c1 = max(calls, key=lambda s: s[2] - s[1])
            say(f"slowest traced {name}: {c1 - c0!r} s, device busy {tr.busy.covered(c0, c1)!r} s "
                f"in it; top ops {trace_mod.top_ops(tr, c0, c1, 3)}; "
                f"idle by span {trace_mod.idle_by_activity(tr, c0, c1, 3)}")

    ev = answered(stage, mix, log, seed)
    calls_s = [end - start for start, end, _ in log.calls]
    attempted = log.builds if mix["op"] == "build" else len(log.scheduled)
    stage.searcher = stage.warm_clusters = ctx = log = None  # freed before the reference runs
    t_check = time.perf_counter()
    numbers = check(stage, config, seed, ev)
    numbers["compiles_in_window"] = in_window
    correct, table = reference.verdict(numbers, {**config["limits"], "compiles_in_window": 0})
    say(f"setup_s {setup_s!r}, window {w1 - w0!r} s, {attempted} requests in {len(calls_s)} calls "
        f"of {float(np.min(calls_s))!r} / {float(np.median(calls_s))!r} / {float(np.max(calls_s))!r} s "
        f"(min / median / max), "
        f"check {time.perf_counter() - t_check!r} s")
    result = {"correct": correct, "attempted": int(attempted),
              "failed": int(reference.bad_rows(ev.answers, stage.corpus_host.shape[0]).sum()),
              "metrics": values, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = table  # the last key of the line
    return result


def print_result(result: dict) -> None:
    for name, v in result["check"].items():
        say(f"check {name} = {float(v['value'])!r} (limit {v['limit']!r})")
    print(json.dumps(result), flush=True)


def require_chip(chips: int) -> None:
    """Exit without a result unless JAX sees enough TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        say(f"no TPU: JAX found {devices[0].platform}; no result")
        sys.exit(1)
    if len(devices) < chips:
        say(f"{chips} chips asked, {len(devices)} found; no result")
        sys.exit(1)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    if not (SRC / "repro" / "vdms").is_dir():
        say(f"the program is not beside the benchmark (no {SRC / 'repro'}); no result")
        return 2
    sys.path.insert(0, str(SRC))
    spec = load_spec()
    cell, config, mix = load_cell(args.workload, spec)
    metrics = cell_metrics(spec, cell["name"], per_layer=bool(args.trace))

    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    require_chip(int(cell["chips"]))
    result = run_cell(cell, config, mix, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), metrics=metrics)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
