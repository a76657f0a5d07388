"""The benchmark's run: set-up, warm-up, the measured window, the check.

Everything that belongs to one configuration, one traffic mix or one
metric is found by name under the benchmark's directory:

* ``BENCHMARK.json`` names the cell's configuration file and its traffic
  mix, ``chipbench/traffic/<traffic>.json``;
* each metric is read by ``chipbench/metrics/<metric name>.py``, whose
  ``read(run)`` returns a number, or None when it finds nothing to read;
  a metric split by the cells' end-to-end metrics, such as
  ``host_ms_per_round.lat``, is read by the file of its name up to the
  first dot.

The configuration names the engine.  Without ``replicas`` it is one
``OverlayServer``; with ``replicas: N`` it is a ``ShardedOverlayServer``
of N replicas, one on each of the cell's chips, with the engine's default
router (``bank_capacity`` is then each replica's).  Every other engine
setting is the engine's default.  The window drives the engine's serving
entry, ``submit``, with results delivered by ``sched.pump.AutoPump``, the
drain the gateway sits on.  A fleet's traffic may also drain a replica
and add one back at set times of the window (``events``, see
``chipbench/traffic.py``); each runs under the engine lock, between two
of the pump's steps.  The load generator claims each result as the pump
delivers it.  After the window every request due in it is drained, and
every delivered answer is compared with the plain reference
(``chipbench/reference.py``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import importlib.util
import json
import threading
import time
from pathlib import Path

import numpy as np

from chipbench import catalog, reference
from chipbench import traffic as tr

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: how long after the window closes a due request may still be delivered
DRAIN_S = 60.0
#: a wait of the generator's loop never sleeps past this, in seconds
POLL_S = 0.05
#: seconds of the window, from its start, that a ``--trace 1`` run reads
#: from its trace.  The profiler keeps a bounded number of device events:
#: a trace of a whole 51-s window of faas64.steady held no device op
#: after its first 16 s.
TRACE_S = 10.0
#: the longest a fleet's warm-up of every round size may take: a first
#: run compiles them all, on every device in turn
WARM_SHAPES_S = 1000.0


class BenchError(RuntimeError):
    """The cell cannot run here (no accelerator, too few chips, bad file)."""


# ------------------------------------------------------------------ cells
@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list            # BENCHMARK.json entries of end_to_end metrics
    layer_metrics: list      # ... and of per_layer metrics

    @property
    def bench_dir(self) -> Path:
        return self.root / "chipbench"


def _applies(entry: dict, cell: str, e2e_cells: dict) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    if "moves" in entry:       # a per-layer metric follows its end-to-end one
        return cell in e2e_cells.get(entry["moves"], ())
    return True


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = [w for w in bench["workloads"] if w["name"] == name]
    if not work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = work[0]
    cfg = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
    names = [x["name"] for x in bench["workloads"]]
    e2e_cells = {m["name"]: [c for c in names if _applies(m, c, {})]
                 for m in bench["end_to_end"]}
    return Cell(root=root, name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                metrics=[m for m in bench["end_to_end"]
                         if _applies(m, name, e2e_cells)],
                layer_metrics=[m for m in bench["per_layer"]
                               if _applies(m, name, e2e_cells)])


def reader(bench_dir: Path, metric: str):
    """``read`` of ``<bench_dir>/metrics/<name>.py``, where ``name`` is
    ``metric`` up to its first dot."""
    path = bench_dir / "metrics" / f"{metric.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -------------------------------------------------------------- compiles
class CompileClock:
    """Every XLA compile, by function: JAX reports each with its seconds,
    whether it compiled or read the persistent compile cache (the event
    wraps both; a cache read is marked by an event just before it)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.log: list[tuple[str, float, bool]] = []
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on_event(self, event, **_):
        if event == self.HIT:
            self._hit = True

    def _on(self, event, duration, fun_name="?", **_):
        if event == self.EVENT:
            self.log.append((fun_name, duration, self._hit))
            self._hit = False

    def mark(self) -> int:
        return len(self.log)

    def summary(self, since: int = 0, until: int | None = None) -> str:
        part = self.log[since:until]
        by: dict[str, list] = {}
        for name, sec, hit in part:
            row = by.setdefault(name, [0, 0, 0.0])
            row[0] += 1
            row[1] += hit
            row[2] += sec
        top = sorted(by.items(), key=lambda kv: -kv[1][2])[:6]
        return (f"{len(part)} ({sum(r[1] for _, r in by.items())} from the "
                f"cache, {sum(r[2] for _, r in by.items()):.2f} s): "
                + ", ".join(f"{n} {c}/{h} {t:.2f} s" for n, (c, h, t) in top))


# ------------------------------------------------------ the load generator
def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class _Spanned:
    """The server as the pump sees it: each ``pump_once`` in a span, and
    before it the calls other threads posted with ``call``.

    The pump holds its engine lock through every ``pump_once``, so a
    posted call runs under that lock, between two of the pump's steps,
    as a client's call into the pump would."""

    def __init__(self, server):
        self._server = server
        self._calls: collections.deque = collections.deque()

    def __getattr__(self, name):
        return getattr(self._server, name)

    def call(self, fn, name: str, timeout: float = 4 * DRAIN_S) -> tuple:
        """Run ``fn(server)`` on the pump's thread at its next step, in a
        span ``name``, and wait for it.  Returns ``fn``'s result, the
        ``perf_counter`` at which it started and the seconds it held the
        lock; raises what ``fn`` raised."""
        done, box = threading.Event(), {}
        self._calls.append((fn, name, box, done))
        if not done.wait(timeout):
            raise BenchError(f"{name}: the pump did not run it within "
                             f"{timeout:.0f} s")
        if "error" in box:
            raise box["error"]
        return box["result"], box["t0"], box["held_s"]

    def pump_once(self):
        ran = bool(self._calls)
        while self._calls:
            fn, name, box, done = self._calls.popleft()
            box["t0"] = time.perf_counter()
            try:
                with span(name):
                    box["result"] = fn(self._server)
            except Exception as e:   # handed to the caller, not the pump
                box["error"] = e
            box["held_s"] = time.perf_counter() - box["t0"]
            done.set()
        with span("bench.pump_once"):
            return self._server.pump_once() or ran


class LoadGenerator:
    """The load generator: submits on its schedule, claims as delivered.

    One thread (the caller's) submits and claims; the pump's thread
    serves.  Times are ``time.perf_counter`` seconds."""

    def __init__(self, pump, kernels, blocks):
        self.pump = pump
        self.kernels = kernels
        self.blocks = blocks
        self._delivered = threading.Event()
        pump.add_tick_listener(self._tick)

    def _tick(self, worked: bool) -> None:
        if worked:
            self._delivered.set()

    def _submit(self, r: tr.Request, outstanding: dict) -> None:
        blk = self.blocks[r.kernel]
        xs = [blk[j, r.offset:r.offset + r.length]
              for j in range(blk.shape[0])]
        with span("bench.submit"):
            t = self.pump.submit(self.kernels[r.kernel], xs, tenant=r.tenant)
        r.t_submit = time.perf_counter()
        outstanding[t] = r

    def _claim(self, outstanding: dict) -> list:
        self._delivered.clear()
        if not outstanding:
            return []
        with span("bench.claim"):
            got = self.pump.try_results(list(outstanding))
        now = time.perf_counter()
        done = []
        for t, outs in got.items():
            if isinstance(outs, KeyError):
                raise outs
            r = outstanding.pop(t)
            r.t_claim, r.outs = now, outs
            done.append(r)
        return done

    def _wait(self, until: float) -> None:
        with span("bench.wait"):
            self._delivered.wait(max(0.0, min(until - time.perf_counter(),
                                              POLL_S)))

    def open_loop(self, reqs: list, start: float, seconds: float,
                  events: list = ()) -> dict:
        """Submit each request at ``start + due``, and call each of
        ``events``, ``(when, fire)`` pairs in order of ``when``, at
        ``when``; claim until the window closes.  Submits and events due
        at one time go in that order.  Returns the requests still
        outstanding."""
        end = start + seconds
        outstanding: dict = {}
        i = j = 0
        while True:
            now = time.perf_counter()
            while True:
                due = start + reqs[i].due if i < len(reqs) else np.inf
                when = events[j][0] if j < len(events) else np.inf
                if min(due, when) > now:
                    break
                if due <= when:
                    self._submit(reqs[i], outstanding)
                    i += 1
                else:
                    events[j][1]()
                    j += 1
            if self._delivered.is_set():
                self._claim(outstanding)
            if (i >= len(reqs) and j >= len(events)
                    and time.perf_counter() >= end):
                return outstanding
            self._wait(min(due, when, end))

    def drain(self, outstanding: dict, deadline: float) -> None:
        while outstanding and time.perf_counter() < deadline:
            self._wait(deadline)
            self._claim(outstanding)
        self._claim(outstanding)


# ------------------------------------------------------------------- run
@dataclasses.dataclass
class Run:
    """What a run hands the metric readers and the check."""

    cell: Cell
    specs: list
    blocks: list
    setup_s: float
    seconds: float
    start: float               # perf_counter at the window's start
    reqs: list                 # requests due in the window
    stats0: dict
    stats1: dict
    trace: dict | None = None
    trace_end: float | None = None   # perf_counter where the trace read ends
    device: dict = dataclasses.field(default_factory=dict)
    events: list = dataclasses.field(default_factory=list)  # Rig.events
    window_compiles: int = 0          # XLA compiles inside the window

    @property
    def end(self) -> float:
        return self.start + self.seconds

    def delivered_in_trace(self) -> list:
        """The requests claimed in the part of the window the trace
        reduction reads."""
        return [r for r in self.reqs
                if r.t_claim is not None and r.t_claim <= self.trace_end]

    def latencies_s(self) -> np.ndarray:
        """Due-to-claim seconds of every request due in the window; a
        request never delivered counts as infinitely late."""
        return np.array([(r.t_claim if r.t_claim is not None else np.inf)
                         - (self.start + r.due) for r in self.reqs])

    def peak(self, key: str) -> float:
        table = json.loads((self.cell.bench_dir / "peaks.json").read_text())
        kind = self.device.get("kind")
        if kind not in table["devices"]:
            raise BenchError(f"no peaks for device {kind!r} in peaks.json")
        return float(table["devices"][kind][key])

    def stat_delta(self, key: str) -> float:
        return float(self.stats1[key]) - float(self.stats0[key])

    def stage_delta(self, key: str) -> float:
        return (float(self.stats1["stage_walls"][key])
                - float(self.stats0["stage_walls"][key]))


def reference_blocks(run: Run, dtype=np.float32) -> dict:
    """The reference's answer for each used kernel's whole input block."""
    return {k: reference.evaluate(run.specs[k], list(run.blocks[k]), dtype)
            for k in sorted({r.kernel for r in run.reqs})}


def check_numbers(run: Run) -> tuple[dict, int]:
    """The compared numbers, each beside its limit (the configuration's
    ``compare``): the widest gap of any answer from the reference, and
    the requests with no answer; and how many requests failed either."""
    limits = run.cell.config["compare"]
    want = reference_blocks(run)
    worst, missing, failed = 0.0, 0, 0
    for r in run.reqs:
        if r.outs is None:
            missing += 1
            failed += 1
            continue
        g = reference.gap(r.outs, [y[r.offset:r.offset + r.length]
                                   for y in want[r.kernel]])
        worst = max(worst, g)
        failed += g > limits["max_gap"]
    return ({"max_gap": {"value": worst, "limit": limits["max_gap"]},
             "undelivered": {"value": missing,
                             "limit": limits["undelivered"]}}, failed)


def put_control(run: Run) -> None:
    """The control in the program's place: every delivered answer
    replaced by the reference computed in bfloat16, the precision below
    the configuration's float32."""
    low = reference_blocks(run, reference.bfloat16())
    for r in run.reqs:
        if r.outs is not None:
            r.outs = [y[r.offset:r.offset + r.length] for y in low[r.kernel]]


def verdict(compared: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in compared.values())


def setup_jax(root: Path) -> None:
    """The compile cache inside the checkout (or where the environment
    says), caching every program however fast it compiled."""
    import os
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(root / ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def make_blocks(specs, config: dict, seed: int) -> list:
    rng = tr.rng_for(seed, tr.STREAM_BLOCKS)
    lo, hi = config["input_range"]
    return [rng.uniform(lo, hi, (len(s.inputs), config["block_len"]))
            .astype(np.float32) for s in specs]


#: what a traffic file's event may do to a fleet
EVENT_KINDS = ("drain", "add")
#: bank counters that a fleet's ``stats()`` leaves per replica
BANK_COUNTERS = ("loads", "hits")


def fleet_size(config: dict, chips: int) -> int | None:
    """The configuration's ``replicas``, None for one engine.  A fleet
    holds one replica on each of the cell's chips."""
    if "replicas" not in config:
        return None
    n = config["replicas"]
    if isinstance(n, bool) or not isinstance(n, int) or n != chips:
        raise BenchError(f"replicas {n!r}: a fleet holds one replica on "
                         f"each of the cell's {chips} chips")
    return n


def make_engine(config: dict, replicas: int | None, devices: list):
    """The configuration's engine: one ``OverlayServer``, or a
    ``ShardedOverlayServer`` of ``replicas`` on ``devices``, each on a
    device of its own."""
    from repro.launch.serve import OverlayServer, ShardedOverlayServer
    bank = int(config["bank_capacity"])
    if replicas is None:
        return OverlayServer(bank_capacity=bank)
    fleet = ShardedOverlayServer(n_replicas=replicas, bank_capacity=bank,
                                 devices=devices)
    # the engine wraps replicas onto shared devices without a word
    placed = len({d.id for d in fleet.devices})
    if placed < replicas:
        raise BenchError(f"{replicas} replicas placed on {placed} distinct "
                         f"devices")
    return fleet


def replica_events(traffic: dict, replicas: int | None) -> list[dict]:
    """The traffic's ``events`` in order of ``at``, each checked against
    the fleet it meets then."""
    events = traffic.get("events", [])
    if events and replicas is None:
        raise BenchError("the traffic's events drain and add replicas; the "
                         "configuration has one engine")
    for e in events:
        if not isinstance(e, dict) or e.get("do") not in EVENT_KINDS:
            raise BenchError(f"event {e!r}: 'do' is one of {EVENT_KINDS}")
        at = e.get("at")
        if (isinstance(at, bool) or not isinstance(at, (int, float))
                or not 0.0 <= at <= 1.0):
            raise BenchError(f"event {e!r}: 'at' is a share of the window, "
                             f"0..1")
        keys = {"at", "do", "replica"} if e["do"] == "drain" else {"at", "do"}
        if set(e) != keys:
            raise BenchError(f"event {e!r}: a {e['do']} has the keys "
                             f"{sorted(keys)}")
    events = sorted(events, key=lambda e: e["at"])
    size = replicas
    for e in events:
        if e["do"] == "add":
            size += 1
            continue
        i = e["replica"]
        if isinstance(i, bool) or not isinstance(i, int) or not 0 <= i < size:
            raise BenchError(f"event {e!r}: the fleet has replicas "
                             f"0..{size - 1} then")
        if size == 1:
            raise BenchError(f"event {e!r}: drains the last replica")
        size -= 1
    return events


class Rig:
    """The set-up of a run: the catalog compiled by the program, the
    seeded input blocks, and the configuration's engine behind its pump,
    warmed up on the cell's own traffic from a seed stream the window
    does not use."""

    def __init__(self, cell: Cell, seed: int, require_tpu: bool = True,
                 log=print, use_cache: bool = True):
        if use_cache:
            setup_jax(cell.root)
        import jax
        from repro.sched.pump import AutoPump

        c = cell.config
        if c["dtype"] != "float32":
            raise BenchError(f"dtype {c['dtype']!r}: only float32 is served")
        replicas = fleet_size(c, cell.chips)
        self.event_plan = replica_events(cell.traffic, replicas)
        self.cell, self.seed, self.log = cell, seed, log
        self.device = device_info(cell.chips, require_tpu)
        self.clock = CompileClock()
        self.specs = catalog.build(c["catalog"], c["input_range"])
        kernels = [catalog.to_program(s) for s in self.specs]
        self.blocks = make_blocks(self.specs, c, seed)
        self.fleet = replicas is not None
        self.server = make_engine(c, replicas, jax.devices()[:cell.chips])
        st = self.server.stats()
        self.served = _Spanned(self.server)
        self.pump = AutoPump(self.served)
        self.gen = LoadGenerator(self.pump, kernels, self.blocks)
        #: bank counters of the replicas drained so far
        self.retired = dict.fromkeys(BANK_COUNTERS, 0)
        #: what each event of the last phase that fired them did
        self.events: list[dict] = []
        engine = (f"{replicas} replicas on "
                  f"{', '.join(str(d) for d in self.server.devices)}; "
                  if self.fleet else "")
        executor = st["per_replica"][0] if self.fleet else st
        log(f"{engine}executor: {executor['executor']}; catalog "
            f"{len(self.specs)} kernels; bank {c['bank_capacity']}; device "
            f"{self.device['kind']} x{self.device['count']}")

    def stats(self) -> dict:
        """The pump's ``stats()``; a fleet's with its bank loads and hits
        summed over its replicas, the drained ones included.  (Events
        fire from the thread that reads this, so none runs meanwhile.)"""
        s = self.pump.stats()
        if self.fleet:
            for k in BANK_COUNTERS:
                s[k] = sum(p[k] for p in s["per_replica"]) + self.retired[k]
        return s

    def _drain(self, fleet, i: int) -> dict:
        bank, device = fleet.replicas[i].bank, str(fleet.devices[i])
        out = fleet.drain_replica(i)
        for k in BANK_COUNTERS:
            self.retired[k] += bank.stats()[k]
        return dict(out, device=device)

    @staticmethod
    def _add(fleet) -> dict:
        i = fleet.add_replica()
        return {"replica": i, "device": str(fleet.devices[i])}

    def _fire(self, event: dict, start: float, when: float) -> None:
        """Run ``event`` under the engine lock; record what it did."""
        fired = time.perf_counter()
        if event["do"] == "drain":
            fn = functools.partial(self._drain, i=event["replica"])
        else:
            fn = self._add
        out, t0, held = self.served.call(fn, "bench.event")
        self.events.append(dict(event, due_s=when - start,
                                fired_s=fired - start, lock_wait_s=t0 - fired,
                                held_s=held, result=out))

    def phase(self, traffic: dict, stream: int, seconds: float,
              on_start=None, events: bool = False):
        """One phase of ``traffic``: its requests, the start time and the
        requests still outstanding at its close.  ``on_start()`` runs
        just before the first submit.  With ``events`` the traffic's
        replica events fire at their times (``self.events``)."""
        c, d, n = self.cell.config, self.gen, len(self.specs)
        reqs = tr.open_schedule(traffic, n, int(c["tenants"]),
                                int(c["block_len"]), seconds,
                                tr.rng_for(self.seed, stream))
        if on_start:
            on_start()
        start = time.perf_counter()
        plan = []
        if events:
            self.events = []
            for e in self.event_plan:
                when = start + e["at"] * seconds
                plan.append((when, functools.partial(self._fire, e, start,
                                                     when)))
        return reqs, start, d.open_loop(reqs, start, seconds, plan)

    def warm_shapes(self) -> int:
        """Serve in set-up one round of every size the window's rounds can
        take, up to the traffic file's ``warmup_tiles_up_to``: 1, 2 and
        4 tiles, then every multiple of 8.  The program compiles per
        round size (its executor per power of two, its live-row gather in
        collect per multiple of 8), so a round of a size not served here
        compiles in the window.  Each round is one request of the catalog
        kernel with the fewest inputs, through the same submit path and
        pump as the traffic; in a fleet, by every replica on its own
        device (each device compiles its own), one replica after
        another, under the engine lock (warmed at once, a thread each, the
        replicas contend for the compile cache's file lock).  Returns the
        rounds served by each replica."""
        up_to = int(self.cell.traffic["warmup_tiles_up_to"])
        sizes = [t for t in (1, 2, 4) if t <= up_to] + list(
            range(8, up_to + 1, 8))
        k = min(range(len(self.specs)),
                key=lambda i: len(self.specs[i].inputs))
        kernel, tile = self.gen.kernels[k], self.server.tile

        def inputs(t):
            return [np.zeros(t * tile, np.float32)
                    for _ in self.specs[k].inputs]

        def serve(fleet):
            for rep in fleet.replicas:
                for t in sizes:
                    rep.submit(kernel, inputs(t))
                    rep.flush()

        with span("bench.warmup"):
            if self.fleet:
                self.served.call(serve, "bench.warmup",
                                 timeout=WARM_SHAPES_S)
            else:
                for t in sizes:
                    self.pump.result(self.pump.submit(kernel, inputs(t)),
                                     timeout=4 * DRAIN_S)
        return len(sizes)

    def warm_up(self) -> int:
        """Warm the shapes, then serve ``warmup_s`` of the cell's traffic
        and wait until idle; returns the requests served.  The traffic's
        events do not fire here."""
        self.log(f"warm-up round sizes: {self.warm_shapes()} rounds")
        with span("bench.warmup"):
            reqs, _, out = self.phase(self.cell.traffic, tr.STREAM_WARMUP,
                                      float(self.cell.traffic["warmup_s"]))
            self.gen.drain(out, time.perf_counter() + 4 * DRAIN_S)
            self.pump.wait_idle(timeout=DRAIN_S)
        if out:
            raise BenchError(f"warm-up left {len(out)} requests undelivered")
        return len(reqs)

    def close(self) -> None:
        self.pump.close()
        self.server = self.served = self.pump = self.gen = None

    def round_tiles(self) -> list:
        """Tiles of each round served so far, from the engine's step log."""
        steps = getattr(self.server.telemetry, "steps", lambda: [])()
        return [int(r["tiles"]) for r in steps if "tiles" in r]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True, log=print,
             trace_dir: str | None = None, use_cache: bool = True,
             trace_s: float = TRACE_S) -> Run:
    """Set up, warm up, measure ``seconds``, drain.  With ``trace`` the
    profiler traces the window, of which the reduction reads the first
    ``trace_s`` seconds.  Returns the run with every request's answer;
    the check is ``check_numbers``."""
    import jax
    rig = Rig(cell, seed, require_tpu, log, use_cache)
    marks = {}

    def on_start():
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            # the benchmark's own spans only, not the runtime's
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # a span made before the profiler starts is never recorded
        marks["window"] = span("bench.window")
        marks["window"].__enter__()
        marks["setup_s"] = time.perf_counter() - t_process

    try:
        n_warm = rig.warm_up()
        mark = rig.clock.mark()
        round0 = len(rig.round_tiles())
        stats0 = rig.stats()
        reqs, start, out = rig.phase(cell.traffic, tr.STREAM_WINDOW, seconds,
                                     on_start, events=True)
        marks["window"].__exit__(None, None, None)
        if trace:
            jax.profiler.stop_trace()
        stats1 = rig.stats()
        mark_end = rig.clock.mark()
        rounds = rig.round_tiles()[round0:]
        # a minute past the close, counted from when the harness is back
        # to claiming (writing the trace out takes a while)
        rig.gen.drain(out, time.perf_counter() + DRAIN_S)
        rig.device["memory_peak_bytes"] = memory_peak_bytes()
    finally:
        rig.close()
    lateness = np.array([r.t_submit - (start + r.due) for r in reqs
                         if r.t_submit is not None])
    if lateness.size:
        log(f"generator lateness: p50 {np.percentile(lateness, 50) * 1e3:.3f}"
            f" ms, p95 {np.percentile(lateness, 95) * 1e3:.3f} ms, max "
            f"{lateness.max() * 1e3:.3f} ms over {lateness.size} submits")
    log(f"compiles in set-up: {rig.clock.summary(0, mark)}")
    log(f"compiles in the window: {rig.clock.summary(mark, mark_end)}")
    log(f"warm-up requests {n_warm}; window requests {len(reqs)}; "
        f"largest round in the window {max(rounds, default=0)} tiles "
        f"(warmed up to {cell.traffic['warmup_tiles_up_to']})")
    run = Run(cell=cell, specs=rig.specs, blocks=rig.blocks,
              setup_s=marks["setup_s"], seconds=seconds, start=start,
              reqs=reqs, stats0=stats0, stats1=stats1,
              trace_end=start + min(trace_s, seconds) if trace else None,
              device=rig.device, events=rig.events,
              window_compiles=mark_end - mark)
    if run.events:
        log_events(run, log)
    return run


def log_events(run: Run, log) -> None:
    """Each event's times and lock, and the latency of the requests due
    before, between and after the events."""
    for e in run.events:
        log(f"event {e['do']} at {e['at']}: due {e['due_s']:.4f} s, fired "
            f"{e['fired_s']:.4f} s, waited {e['lock_wait_s']:.6f} s for the "
            f"lock and held it {e['held_s']:.6f} s: {e['result']}")
    lat = run.latencies_s()
    due = np.array([r.due for r in run.reqs])
    cuts = [0.0] + [e["due_s"] for e in run.events] + [np.inf]
    for a, b in zip(cuts, cuts[1:]):
        part = lat[(due >= a) & (due < b)] * 1e3
        if part.size:
            log(f"requests due {a:.3f}..{min(b, run.seconds):.3f} s: "
                f"{part.size}, p50 {np.percentile(part, 50):.4f} ms, p95 "
                f"{np.percentile(part, 95):.4f} ms")


def read_metrics(run: Run, entries: list) -> dict:
    out = {}
    for m in entries:
        v = reader(run.cell.bench_dir, m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
