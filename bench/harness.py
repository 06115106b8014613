"""Drive one cell through ``MMOEngine.submit`` and measure it.

One run: build the engine the configuration states, make every input from
``--seed``, warm up every program the cell's traffic will use (set-up ends
at the first due request), serve the window with the engine's background
loop running as a client would, then check a sample of what the window
served against the plain references, and (``--trace 1``) reduce a profiler
trace of the window to the per-layer metrics.

Latency is the client's: from each request's due time to the moment the
client sees its future done.  In the open loop one client thread sends at
the due times and polls its outstanding futures every ``POLL_S``; in the
closed loop each client blocks on its future.  A request that fails, or
that has not finished ``grace_s`` after the window closed, counts as failed
and as missing every latency limit.
"""
from __future__ import annotations

import dataclasses
import math
import queue
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from bench import generator, inputs, peaks, reference, spec, trace_reduce

# no fallback may hide the device: a refused kernel fails the run
STRICT = dict(breaker_threshold=None, transient_retries=0, bisect=False)
POLL_S = 0.0005
COMPILE_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/compile_requests")


def log(msg: str) -> None:
  print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Served:
  """One request of the window, as the client saw it."""
  app: str
  n: int
  request: object          # the ProblemRequest submitted
  adj: np.ndarray          # its input (relabelled base input, closed loop)
  due_s: float
  base: int = -1           # closed loop: index of the base input
  perm: object = None      # closed loop: the relabelling
  future: object = None
  sent_s: float = math.nan
  done_s: float = math.nan
  outcome: str = "unsent"
  result: object = None

  @property
  def latency_s(self) -> float:
    return self.done_s - self.due_s if self.outcome == "done" else math.inf


@dataclasses.dataclass
class Run:
  """What a per-layer metric reader sees of one traced run."""
  served: list
  t0: float                # window start (perf_counter seconds)
  t1: float                # window end
  events: list             # the engine's flight-recorder events
  trace: object            # trace_reduce.Reduced over [t0, t1]
  peak: peaks.Peak


class CompileCounter:
  """Counts JAX traces and compiles (persistent-cache loads included)."""

  def __init__(self):
    import jax
    self.count = 0
    self.names: list = []
    jax.monitoring.register_event_listener(self._event)
    jax.monitoring.register_event_duration_secs_listener(self._duration)

  def _event(self, name, **_):
    if name.startswith(COMPILE_EVENTS):
      self.count += 1
      self.names.append(name)

  def _duration(self, name, _secs, **_):
    self._event(name)


def build_engine(cfg: dict, devices, interpret):
  from repro.serve_mmo import MMOEngine
  kw = dict(cfg["engine"])
  if cfg.get("mesh"):
    from repro.core.distributed import make_mesh
    shape = tuple(cfg["mesh"])
    kw["mesh"] = make_mesh(shape, devices=devices[:math.prod(shape)])
  return MMOEngine(interpret=interpret, **kw, **STRICT)


def make_request(cfg: dict, app: str, adj):
  from repro.serve_mmo import closure_request
  return closure_request(adj, op=inputs.APPS[app].op,
                         algorithm=cfg["algorithm"])


def degree(cfg: dict, app: str) -> float:
  return float(cfg["apps"][app]["degree"])


def base_inputs(cfg: dict, base, seed: int) -> dict:
  """The closed loop's base graphs, index → adjacency, drawn from
  ``--seed``; each send is one of them relabelled."""
  rng = generator.rng_for(seed, 4)
  return {i: inputs.make_input(app, n, degree(cfg, app), rng)
          for i, (app, n, _) in enumerate(base)}


def warm_up(engine, cfg: dict, shapes, concurrency: int, seed: int) -> int:
  """Run each (app, n) bucket the traffic can form once at every batch size
  it can reach, synchronously, before the loop starts: compiles (or loads)
  and first-executes exactly the programs the window will use.  Returns the
  number of warm requests served."""
  from repro.serve_mmo.scheduler import request_bucket
  rng = generator.rng_for(seed, 5)
  buckets = {}
  for app, n in sorted(shapes):
    req = make_request(cfg, app, inputs.make_input(app, n, degree(cfg, app),
                                                   rng))
    buckets.setdefault(request_bucket(req), req)
  if cfg["engine"].get("mode") == "arena":
    sizes = [1]
  else:
    cap = min(int(cfg["engine"].get("max_batch", 8)), concurrency)
    sizes = sorted({cap} | {1 << i for i in range(cap.bit_length())
                            if 1 << i <= cap})
  served = 0
  for req in buckets.values():
    for size in sizes:
      futs = [engine.submit(dataclasses.replace(req)) for _ in range(size)]
      engine.run_until_idle()
      for f in futs:
        f.result()
      served += size
  return served


def poll(pending: list) -> None:
  """Stamp the completion of every pending request that is now done."""
  now = time.perf_counter()
  still = []
  for s in pending:
    if s.future.done():
      s.done_s = now
    else:
      still.append(s)
  pending[:] = still


def window_open(engine, served: list, t_end: float) -> tuple:
  """Send each request at its due time from this thread, stamping
  completions by polling, until ``t_end``; returns the generator's lag per
  send and the requests still pending.  A generator running late still
  sends every request of the window, and its lag counts in the latency."""
  lags, pending = [], []
  i = 0
  while i < len(served) or time.perf_counter() < t_end:
    now = time.perf_counter()
    while i < len(served) and served[i].due_s <= now:
      s = served[i]
      s.sent_s = time.perf_counter()
      s.future = engine.submit(s.request)
      lags.append(s.sent_s - s.due_s)
      pending.append(s)
      i += 1
    poll(pending)
    nxt = served[i].due_s if i < len(served) else t_end
    time.sleep(max(0.0, min(POLL_S, nxt - time.perf_counter())))
  return lags, pending


def window_closed(engine, feed, clients: int, t0: float, seconds: float,
                  round_len: int) -> list:
  """``clients`` callers, each sending its next request when the previous
  one answered, in whole rounds of ``round_len`` sends: the window ends at
  the round boundary nearest ``seconds`` (a new round starts while half a
  round as long as the last still fits).  Returns what they sent."""
  sent, lock = [], threading.Lock()
  state = {"taken": 0, "round_t0": t0, "round_s": 0.0}

  def client():
    while True:
      with lock:
        now = time.perf_counter()
        if state["taken"] % round_len == 0:
          if state["taken"]:
            state["round_s"] = now - state["round_t0"]
            state["round_t0"] = now
            if now + 0.5 * state["round_s"] > t0 + seconds:
              return
        state["taken"] += 1
      s = feed.get()
      s.due_s = s.sent_s = time.perf_counter()
      s.future = engine.submit(s.request)
      try:
        s.future.result()
      except Exception:  # noqa: BLE001 — a failed request is counted below
        pass
      s.done_s = time.perf_counter()
      with lock:
        sent.append(s)

  threads = [threading.Thread(target=client, name=f"bench-client-{k}")
             for k in range(clients)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()
  return sorted(sent, key=lambda s: s.sent_s)


def settle(served: list) -> None:
  """Record each request's outcome and answer once the window is over."""
  for s in served:
    if s.future is None:
      continue
    if not s.future.done():
      s.outcome = "unfinished"
      continue
    try:
      s.result = s.future.result(timeout=0)
      s.outcome = "done"
    except Exception as e:  # noqa: BLE001 — counted as failed
      s.outcome = type(e).__name__


def nearest_rank(values, q: float) -> float:
  v = sorted(values)
  return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)] if v else math.inf


def check_sample(cfg: dict, served: list, seed: int) -> list:
  """The answers compared: every closed-loop answer, or in the open loop a
  seeded sample of the completed ones with each application's largest."""
  done = [s for s in served if s.outcome == "done"]
  size = cfg["check"].get("sample")
  if size is None or len(done) <= size:
    return done
  rng = generator.rng_for(seed, 6)
  pick = set(rng.choice(len(done), size, replace=False).tolist())
  for app in {s.app for s in done}:
    pick.add(max((i for i, s in enumerate(done) if s.app == app),
                 key=lambda i: done[i].n))
  return [done[i] for i in sorted(pick)]


def compare_served(cfg: dict, sample: list, bases: dict,
                   precision: str = "float32") -> dict:
  """app-number name → the worst reading over the sample.  ``bases`` maps
  a closed-loop base index to its input, whose reference is computed once
  and relabelled for each send."""
  worst = {reference.number_name(app): 0.0 for app in cfg["apps"]}
  refs = {}
  for s in sample:
    if s.base >= 0:
      if s.base not in refs:
        refs[s.base] = reference.reference(s.app, bases[s.base], precision)
      got = reference.compare(s.app, s.result.value, refs[s.base], s.perm)
    else:
      got = reference.compare(s.app, s.result.value,
                              reference.reference(s.app, s.adj, precision))
    name = reference.number_name(s.app)
    worst[name] = max(worst[name], got)
  return worst


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float, interpret=None) -> dict:
  """One run of ``cell``; returns the result line as a dict."""
  import jax
  cfg, mix = cell.config, cell.traffic
  used = list(devices[:cell.chips])
  engine = build_engine(cfg, used, interpret)
  grace_s = float(mix.get("grace_s", 60.0))
  bases = {}
  if mix["loop"] == "open":
    sched = generator.open_schedule(mix, seed, seconds)
    data = generator.rng_for(seed, 4)
    served = []
    for snd in sched:
      adj = inputs.make_input(snd.app, snd.n, degree(cfg, snd.app), data)
      served.append(Served(snd.app, snd.n, make_request(cfg, snd.app, adj),
                           adj, snd.due_s))
    shapes = {(s.app, s.n) for s in served}
    concurrency = int(cfg["engine"].get("max_batch", 8))
  else:
    base, round_len, order = generator.closed_rounds(mix, seed)
    bases = base_inputs(cfg, base, seed)
    shapes = {(app, n) for app, n, _ in base}
    concurrency = int(mix["clients"])
  warmed = warm_up(engine, cfg, shapes, concurrency, seed)

  feed = stop_feed = None
  if mix["loop"] == "closed":
    # a producer relabels the next sends while the current ones run, so a
    # client never waits on input preparation
    feed = queue.Queue(maxsize=2 * int(mix["clients"]))
    stop_feed = threading.Event()
    relabel_rng = generator.rng_for(seed, 7)

    def produce():
      while not stop_feed.is_set():
        b = next(order)
        app, n, _ = base[b]
        perm = relabel_rng.permutation(n)
        adj = inputs.relabel(bases[b], perm)
        s = Served(app, n, make_request(cfg, app, adj), adj, math.nan,
                   base=b, perm=perm)
        while not stop_feed.is_set():
          try:
            feed.put(s, timeout=0.1)
            break
          except queue.Full:
            continue

    producer = threading.Thread(target=produce, name="bench-feed")
    producer.start()
    while not feed.full():
      time.sleep(0.01)

  compiles = CompileCounter()
  misses0 = engine.cache.misses
  engine.start()
  tracedir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
  if trace:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tracedir, profiler_options=opts)
    anchor_perf = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
      pass
  t0 = time.perf_counter() + 0.01
  setup_s = t0 - t_start
  counted0 = compiles.count
  lags, pending = [], []
  while time.perf_counter() < t0:
    time.sleep(0.001)
  if mix["loop"] == "open":
    for s in served:
      s.due_s += t0
    t1 = t0 + seconds
    lags, pending = window_open(engine, served, t1)
  else:
    served = window_closed(engine, feed, int(mix["clients"]), t0, seconds,
                           round_len)
    t1 = max(s.done_s for s in served)
  if trace:
    jax.profiler.stop_trace()
  in_window = compiles.count - counted0 + engine.cache.misses - misses0
  # requests still running at the close: late, not wrong — wait for them
  deadline = t1 + grace_s
  while pending and time.perf_counter() < deadline:
    poll(pending)
    time.sleep(POLL_S)
  if stop_feed is not None:
    stop_feed.set()
    producer.join()
  engine.stop(drain=False)
  settle(served)

  peak_bytes = 0
  for d in used:
    stats = d.memory_stats()
    if stats is not None:
      peak_bytes = max(peak_bytes, int(stats.get("peak_bytes_in_use", 0)))
  stats = engine.stats()
  events = engine.tracer.events() if trace else []
  dropped = engine.tracer.stats()["dropped"]
  pinned = {tuple(a) for a in cfg["arms"]}
  off_arm = sum(n for (_, backend, sched), n in stats.arms.items()
                if (backend, sched) not in pinned)
  for (label, backend, sched), n in sorted(stats.arms.items()):
    log(f"bucket {label}: {n} launches on {backend}/{sched}")
  del engine

  failed = [s for s in served if s.outcome != "done"]
  for s in failed[:10]:
    log(f"request {s.app} n={s.n} due {s.due_s - t0:.3f}s: {s.outcome}")
  if lags:
    log(f"generator lag: median {1e3 * float(np.median(lags)):.3f} ms, "
        f"max {1e3 * max(lags):.3f} ms over {len(lags)} sends")
  if in_window:
    log(f"{in_window} compiles inside the window: {compiles.names[-5:]}")
  log(f"set-up {setup_s:.3f}s ({warmed} warm requests); window "
      f"{t1 - t0:.3f}s; {len(served)} sent, {len(failed)} failed")

  t_check = time.perf_counter()
  sample = check_sample(cfg, served, seed)
  numbers = compare_served(cfg, sample, bases)
  checks = {k: {"value": v, "limit": float(cfg["check"]["limits"][k])}
            for k, v in numbers.items()}
  checks["unanswered"] = {"value": float(len(failed)), "limit": 0.0}
  checks["compiles_in_window"] = {"value": float(in_window), "limit": 0.0}
  checks["off_pinned_arm"] = {"value": float(off_arm), "limit": 0.0}
  correct = all(c["value"] <= c["limit"] for c in checks.values())
  log(f"checked {len(sample)} answers in {time.perf_counter() - t_check:.1f}s")

  dev = used[0]
  device = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(used), "memory_peak_bytes": peak_bytes}
  out = {"correct": correct, "attempted": len(served), "failed": len(failed)}
  if not trace:
    lat = [s.latency_s for s in served]
    done_in = sum(1 for s in served
                  if s.outcome == "done" and s.done_s <= t1)
    values = {"setup_s": setup_s, "solved_per_s": done_in / (t1 - t0),
              "p50_ms": 1e3 * nearest_rank(lat, 50),
              "p95_ms": 1e3 * nearest_rank(lat, 95)}
    out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                  "unit": m["unit"]}
                      for m in cell.end_to_end}
  else:
    if dropped:
      raise RuntimeError(f"the flight recorder dropped {dropped} events")
    path = trace_reduce.find_xplane(tracedir)
    reduced = trace_reduce.reduce(
        trace_reduce.load(path), t0_s=t0, t1_s=t1, anchor_perf_s=anchor_perf,
        device_ids={d.id for d in used})
    shutil.rmtree(tracedir, ignore_errors=True)
    run = Run(served=served, t0=t0, t1=t1, events=events,
              trace=reduced, peak=peaks.peak_for(dev.device_kind))
    out["metrics"] = {}
    for m in cell.per_layer:
      value = spec.metric_reader(m["name"])(run)
      if value is not None:
        out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    device["busy_s"] = reduced.mean_busy_s
    device["window_s"] = reduced.window_s
    spans = [(e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6, e["name"])
             for e in events if e.get("ph") == "X"]
    out["breakdown"] = {
        "device_ops": [[k, v] for k, v in sorted(
            reduced.op_seconds.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": trace_reduce.attribute_gaps(reduced.gaps, spans)}
  out["device"] = device
  out["checks"] = checks
  for k, c in checks.items():
    log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
  return out
