"""One run of one cell: set up, offer the traffic, measure, check, report.

The process holds one chip.  It makes the weights from the seed on the
device, builds the engine through ``repro.serve.make_engine``, warms every
program shape the cell's traffic uses, offers the traffic for the window,
then checks what the window served against ``bench/reference``.  What
depends on the model's equations (sizes, weights, the reference, the work
counts) comes from the cell's architecture file in ``bench/arch/``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from bench import traffic as traffic_mod
from bench import trace as trace_mod
from bench.peaks import peaks
from bench.spec import ROOT, Cell, metric_reader
from repro.config.run import ServeConfig

CACHE_DIR = ROOT / ".jax_cache"
DRAIN_S = 60.0            # longest wait for a window request's first token
FINISH_S = 60.0           # longest wait for requests to finish for the check
TRACE_S = 4.0             # traced part of the window


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Req:
    item: traffic_mod.Item
    due: float                       # absolute, host clock
    sent: float = 0.0
    rid: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    hit_tokens: int = 0
    error: str = ""

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.item.max_new_tokens


@dataclasses.dataclass
class Run:
    """What the metric readers see."""
    cell: Cell
    shapes: Any                      # the architecture's sizes
    arch: ModuleType                 # the cell's bench/arch/ file
    chips: int
    peaks: Dict[str, float]
    reqs: List[Req]
    win0: float
    win1: float
    stats0: Dict[str, Any]
    stats1: Dict[str, Any]
    trace: Optional[trace_mod.Summary] = None
    trace_t0: float = 0.0            # traced interval, host clock
    trace_t1: float = 0.0
    t_end: float = 0.0               # when the run stopped waiting

    @property
    def seconds(self) -> float:
        return self.win1 - self.win0

    def window_reqs(self) -> List[Req]:
        """Requests due inside the window."""
        return [r for r in self.reqs if self.win0 <= r.due < self.win1]

    def decode_tokens(self, t0: float, t1: float):
        """(tokens, summed live context) of decode-produced tokens that
        reached the client in [t0, t1): token j >= 1 of a request with a
        prompt of L tokens attended over L + j positions."""
        n = ctx = 0
        for r in self.reqs:
            L = len(r.item.prompt)
            for j, t in enumerate(r.times):
                if j and t0 <= t < t1:
                    n += 1
                    ctx += L + j
        return n, ctx

    def prefills(self, t0: float, t1: float):
        """(prompt length, prefix-hit tokens) of admissions whose first
        token reached the client in [t0, t1)."""
        return [(len(r.item.prompt), r.hit_tokens) for r in self.reqs
                if r.times and t0 <= r.times[0] < t1]


class CompileCounter:
    """Programs built (compiled or loaded from the persistent cache) and
    cache hits and misses, from JAX's monitoring events."""

    def __init__(self):
        self.built = self.hits = self.misses = 0
        self.names: List[str] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def snapshot(self) -> Dict[str, int]:
        return {"built": self.built, "hits": self.hits, "misses": self.misses}


def serve_config(c: Dict[str, Any], **over) -> ServeConfig:
    """The program's ``ServeConfig`` from the configuration's ``serve``
    group, greedy and with no end-of-sequence token, updated by ``over``."""
    kw = dict(c["serve"], temperature=0.0, eos_id=-1)
    kw["prefill_buckets"] = tuple(kw["prefill_buckets"])
    kw.update(over)
    return ServeConfig(**kw)


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however quickly it compiles."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(cell: Cell, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    return devs[:cell.chips]


def warm_prompts(cell: Cell, scfg, vocab: int) -> List[np.ndarray]:
    """A primer, then one prompt per prefill bucket the traffic can reach
    (the primer is as long as the first)."""
    mix = cell.traffic
    shared = "system" in mix or mix["turns"]["max"] > 1
    lo = 1 if shared else mix["message"]["min"]
    hi = mix["max_context"] - mix["budget"]["min"]
    buckets = sorted(scfg.prefill_buckets)

    def bucket(n):
        return next((b for b in buckets if b >= n), n)

    rng = np.random.default_rng(0xB0C4E7)
    sizes = [b for b in buckets if bucket(lo) <= b <= bucket(hi)]
    return [rng.integers(0, vocab, size=b, dtype=np.int32)
            for b in sizes[:1] + sizes]


def fillers(engine, longest, system) -> List[np.ndarray]:
    """Distinct prompts, as long as the longest warmed one, whose cached
    pages fill the page pool before the window, as earlier traffic fills a
    server's pool: the window's admissions then evict and spill, as they do
    in a server that has run for a while.  Room is left for ``system``."""
    if engine.pool is None or not longest:
        return []
    size = len(longest[0])
    page = engine.backend.page_size
    need = -(-(size + 2) // page)
    room = engine.pool.free_count() - sum(-(-len(p) // page) + 1
                                          for p in system)
    rng = np.random.default_rng(0xF111)
    return [rng.integers(0, engine.cfg.vocab_size, size=size, dtype=np.int32)
            for _ in range(max(0, room) // need)]


def _serve(engine, prompts) -> None:
    """Serve two tokens of each prompt, then step until the engine idles."""
    for p in prompts:
        engine.submit(p, 2)
    deadline = time.perf_counter() + 1200
    while engine.step():
        if time.perf_counter() > deadline:
            raise RuntimeError("warm-up did not finish")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True,
             limits: Optional[Dict[str, Any]] = None,
             check: bool = True, control: bool = False) -> Dict[str, Any]:
    """Run ``cell`` once; returns the result object the command prints.

    ``check=False`` skips the comparison with the reference (rate sweeps).
    ``control=True`` puts the control in the program's place: the tokens
    compared are those the fp8 computation puts first, and ``correct`` is
    decided from them by the same comparison (bench/control.py); the
    program's own reading is kept under ``readings``."""
    from repro.serve import make_engine, programs   # the system under test

    counter = CompileCounter()
    devs = devices_for(cell, require_tpu)
    dev = devs[0]
    enable_cache()
    phases: Dict[str, Any] = {"import_s": time.perf_counter() - t_process}

    arch = cell.arch
    s = arch.shapes(cell.config)
    cfg = arch.model_config(cell.config_name, cell.config)
    scfg = serve_config(cell.config, **cell.traffic.get("serve", {}))

    t = time.perf_counter()
    c0 = counter.snapshot()
    params = arch.make_params(seed, s)
    jax.block_until_ready(params)
    phases["init_s"] = time.perf_counter() - t
    phases["init_programs"] = _delta(counter.snapshot(), c0)

    t = time.perf_counter()
    engine = make_engine(cfg, params, scfg)
    phases["build_s"] = time.perf_counter() - t

    t = time.perf_counter()
    c0 = counter.snapshot()
    primer, *warm = warm_prompts(cell, scfg, s.vocab)
    # The engine's first call of a program sees freshly made (uncommitted)
    # per-slot arrays, every later call committed ones: a different
    # executable.  The primer takes the first, so each bucket below builds
    # the one the traffic will use.  Then earlier traffic fills the page
    # pool, and one short request over each shared system prompt caches
    # its pages last, as the most recently used.
    system = traffic_mod.system_prompts(cell.traffic, seed, s.vocab)
    _serve(engine, [primer])
    _serve(engine, warm)
    fill = fillers(engine, warm[-1:], system)
    phases["fill_requests"] = len(fill)
    _serve(engine, fill)
    _serve(engine, system)
    if engine.cold is not None:
        # The spill and fault-in page movers run once the pool fills.
        page0 = jax.numpy.asarray(0, jax.numpy.int32)
        blob = programs.read_page_program()(engine.states, page0)
        engine.states = programs.write_page_program()(engine.states, page0,
                                                      blob)
        jax.block_until_ready(engine.states)
    phases["warm_s"] = time.perf_counter() - t
    phases["warm_programs"] = _delta(counter.snapshot(), c0)
    phases["warm_requests"] = len(warm)

    mix = cell.traffic
    backlog = traffic_mod.is_backlog(mix)
    lead_in = 0.0 if backlog else float(mix.get("lead_in_s", 0.0))
    items = traffic_mod.generate(mix, seed, lead_in + seconds, s.vocab)
    setup_s = time.perf_counter() - t_process
    log(f"setup: {json.dumps(phases)} setup_s={setup_s:.3f}")

    loop = _Loop(engine, items, trace, backlog)
    if backlog:
        loop.fill()
    else:
        loop.start(lead_in)
    c_win = counter.snapshot()
    loop.window(seconds)
    compiles_in_window = _delta(counter.snapshot(), c_win)
    compiles_in_window["names"] = counter.names[c_win["built"]:]
    if not backlog:
        loop.drain(DRAIN_S)
    elif engine.scheduler.depth() == 0:
        raise RuntimeError("the backlog drained inside the window: "
                           "make it longer")
    if check:
        plan = _plan(limits or {})
        loop.finish(plan["min_tokens"], plan["max_requests"], FINISH_S)

    memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    log(f"window: kv_pool at start {json.dumps(loop.stats0.get('kv_pool'))}"
        f" at end {json.dumps(loop.stats1.get('kv_pool'))} "
        f"cold_pages={loop.stats1.get('cold_pages')}")
    log(f"window: queued_at_end={loop.stats1.get('queued')} "
        f"compiles={json.dumps(compiles_in_window)} "
        f"lateness_ms={json.dumps(loop.lateness_ms())} "
        f"peak_bytes_in_use={memory_peak}")

    summary = None
    if trace:
        summary = loop.read_trace(cell.chips)
    run = Run(cell, s, arch, cell.chips, peaks(dev.device_kind) if require_tpu
              else peaks("TPU v5 lite"), loop.reqs, loop.win0, loop.win1,
              loop.stats0, loop.stats1, summary, loop.trace_t0, loop.trace_t1,
              loop.t_end)
    for r in loop.reqs:
        if r.rid >= 0:
            r.hit_tokens = int(engine.request(r.rid).prefix_hit_tokens)

    engine.close()
    del engine, params, loop
    gc.collect()

    checks, readings, n_checked = (_check(run, seed, limits or {}, control)
                                   if check else ({}, {}, 0))
    correct = n_checked > 0 and all(v <= lim for v, lim in checks.values())

    metrics = {}
    wanted = cell.layer if trace else cell.e2e
    for m in wanted:
        if m.name == "setup_s":
            v = setup_s
        else:
            v = metric_reader(m.name)(run)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}

    attempted, failed = _attempts(run, backlog)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": [[n, v] for n, v in summary.top_ops],
            "idle_gaps": [[n, v] for n, v in summary.idle_gaps]}
    out["queued_at_end"] = run.stats1.get("queued")
    if readings:
        out["readings"] = readings
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in checks.items()}
    return out


def _delta(a: Dict[str, int], b: Dict[str, int]) -> Dict[str, int]:
    return {k: a[k] - b[k] for k in a}


def _attempts(run: Run, backlog: bool):
    if backlog:
        reqs = [r for r in run.reqs
                if any(run.win0 <= t < run.win1 for t in r.times) or r.error]
    else:
        reqs = run.window_reqs()
    failed = sum(1 for r in reqs if r.error or not r.times)
    return len(reqs), failed


def sample(run: Run, seed: int, min_tokens: int, max_reqs: int) -> List[Req]:
    """Finished requests drawn from the seed, the longest (prompt plus
    served) first, until ``min_tokens`` served tokens or ``max_reqs``."""
    done = [r for r in run.reqs if r.done and not r.error]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.item.prompt) + len(r.tokens),
                                       r.due))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 0xC4EC])
    picked = [longest]
    for i in rng.permutation(len(rest)):
        if (sum(len(r.tokens) for r in picked) >= min_tokens
                or len(picked) >= max_reqs):
            break
        picked.append(rest[i])
    return picked


def _plan(limits: Dict[str, Any]) -> Dict[str, int]:
    return limits.get("sample", {"min_tokens": 256, "max_requests": 6})


def _check(run: Run, seed: int, limits: Dict[str, Any],
           control: bool = False):
    """The numbers compared, each (value, limit); other readings; and how
    many served tokens were compared.  With ``control``, the control's
    tokens stand in the program's place and the program's own widest gap
    goes to the other readings as ``program_widest_logit_gap``."""
    lim = limits.get("widest_logit_gap", {"limit": 0.0})["limit"]
    plan = _plan(limits)
    picked = sample(run, seed, plan["min_tokens"], plan["max_requests"])
    if not picked:
        return {"widest_logit_gap": (float("inf"), lim)}, {}, 0
    t = time.perf_counter()
    reqs = [(r.item.prompt, r.tokens) for r in picked]
    gaps = run.arch.served_gaps(seed, run.shapes, reqs)
    log(f"reference: {len(picked)} requests, {len(gaps)} served tokens, "
        f"{time.perf_counter() - t:.2f} s")
    if not control:
        return {"widest_logit_gap": (float(gaps.max()), lim)}, {}, len(gaps)
    ctl = run.arch.served_gaps(seed, run.shapes, reqs, control=True)
    return ({"widest_logit_gap": (float(ctl.max()), lim)},
            {"program_widest_logit_gap": float(gaps.max())}, len(ctl))


class _Loop:
    """Offers the traffic to the engine and records what comes back."""

    def __init__(self, engine, items, trace: bool, backlog: bool = False):
        self.engine = engine
        self.items = items
        self.next = 0
        self.reqs: List[Req] = []
        self.trace = trace
        self.backlog = backlog
        self.base = 0.0
        self.win0 = self.win1 = 0.0
        self.trace_t0 = self.trace_t1 = self.t_end = 0.0
        self.stats0: Dict[str, Any] = {}
        self.stats1: Dict[str, Any] = {}
        self._logdir: Optional[str] = None
        self._window_span = None

    # -- offering ------------------------------------------------------------
    def _submit_due(self, now: float) -> None:
        while (self.next < len(self.items)
               and self.base + self.items[self.next].due_s <= now):
            it = self.items[self.next]
            self.next += 1
            req = Req(it, self.base + it.due_s)
            req.sent = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.submit"):
                req.rid = self.engine.submit(
                    it.prompt, it.max_new_tokens,
                    on_token=_recorder(req))
            self.reqs.append(req)

    def _step(self) -> bool:
        with jax.profiler.TraceAnnotation("bench.step"):
            return self.engine.step()

    def _run_until(self, until: float, offer: bool) -> None:
        while True:
            now = time.perf_counter()
            if offer:
                self._submit_due(now)
            if now >= until:
                return
            if not self._step():
                nxt = (self.base + self.items[self.next].due_s
                       if offer and self.next < len(self.items) else until)
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(max(0.0, min(nxt, until) - now))

    # -- phases ----------------------------------------------------------------
    def start(self, lead_in: float) -> None:
        self.base = time.perf_counter()
        self._run_until(self.base + lead_in, offer=True)

    def fill(self) -> None:
        """Backlog: queue every request, then step until every slot is
        busy."""
        self.base = time.perf_counter()
        self._submit_due(self.base)
        deadline = self.base + 600
        while self.engine.slots.free_count() > 0:
            self._step()
            if time.perf_counter() > deadline:
                raise RuntimeError("slots never all busy")

    def window(self, seconds: float) -> None:
        self.win0 = time.perf_counter()
        self.win1 = self.win0 + seconds
        self.stats0 = self.engine.stats()
        offer = self.next < len(self.items)
        if self.trace:
            if self.backlog:
                # A backlog's window opens with every slot mid-decode: the
                # trace starts once a request finishes, so that it holds the
                # admission that takes the freed slot.
                self._run_until_a_request_ends(self.win1 - TRACE_S)
            self._logdir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._logdir)
            self.trace_t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                self._run_until(min(self.win1, self.trace_t0 + TRACE_S),
                                offer)
            self.trace_t1 = time.perf_counter()
            jax.profiler.stop_trace()
        self._run_until(self.win1, offer)
        self.stats1 = self.engine.stats()
        self.t_end = time.perf_counter()

    def _run_until_a_request_ends(self, until: float) -> None:
        ended = sum(r.done for r in self.reqs)
        while (time.perf_counter() < until
               and sum(r.done for r in self.reqs) == ended):
            self._step()

    def drain(self, limit_s: float) -> None:
        """Step on, sending nothing more, until every request due in the
        window has its first token."""
        deadline = time.perf_counter() + limit_s
        waiting = [r for r in self.reqs if self.win0 <= r.due < self.win1]
        while any(not r.times for r in waiting):
            if time.perf_counter() > deadline or not self._step():
                break
        self.t_end = time.perf_counter()

    def finish(self, min_tokens: int, max_reqs: int, limit_s: float) -> None:
        """Step on, sending nothing more, until finished requests hold
        ``min_tokens`` served tokens or number ``max_reqs``, so that the
        check has answers to compare: at most ``limit_s`` past the close."""
        deadline = time.perf_counter() + limit_s
        while time.perf_counter() < deadline:
            done = [r for r in self.reqs if r.done]
            if (len(done) >= max_reqs
                    or sum(len(r.tokens) for r in done) >= min_tokens
                    or not self._step()):
                return

    # -- readings ----------------------------------------------------------------
    def lateness_ms(self) -> Dict[str, float]:
        late = np.array([(r.sent - r.due) * 1e3 for r in self.reqs]
                        or [0.0])
        return {"p50": float(np.percentile(late, 50)),
                "p95": float(np.percentile(late, 95)),
                "max": float(late.max())}

    def read_trace(self, chips: int) -> Optional[trace_mod.Summary]:
        try:
            path = trace_mod.find_xplane(Path(self._logdir))
            if path is None:
                log("trace: no .xplane.pb written")
                return None
            prof = jax.profiler.ProfileData.from_file(str(path))
            summary = trace_mod.read_profile(prof, chips)
            log(f"trace: busy_s={summary.busy_s} window_s={summary.window_s} "
                f"modules={json.dumps(summary.modules)}")
            return summary
        finally:
            shutil.rmtree(self._logdir, ignore_errors=True)


def _recorder(req: Req):
    def on_token(tok: int) -> None:
        req.times.append(time.perf_counter())
        req.tokens.append(tok)
    return on_token
