"""One run of one cell: build the served path, fill it as the cell's traffic
needs, drive ``ServeEngine.step`` for the window, check what it served
against the reference, and reduce it all to the cell's metrics.

Everything that belongs to one configuration, traffic mix, metric or cell
sits in a file of its own that is found by name:

- ``configs/<config>.json``: the model's sizes as run, its source, what was
  cut and assumed, and the serving settings (page size, batch, prefill
  budget, KV pool pages); ``references/<reference>.py`` beside it;
- ``traffic/<mix>.json``: parameters of the one generator
  (``chipbench.traffic``);
- ``metrics/<metric>.py``: a reader ``read(rec) -> float | None`` of the run
  record (``Record``); ``None`` leaves the metric out of the line;
- ``limits/<cell>.json``: the limit of each number that decides
  ``correct``, with the readings it was set from.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import roofline, spans as spanlib, traffic, weights
from chipbench.tracereduce import reduce_file

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
# Served tokens the correctness sample aims for, and its most requests.
SAMPLE_TOKENS = 256
SAMPLE_REQUESTS = 24


# -- finding files by name ---------------------------------------------------

def load_json(bench_dir: pathlib.Path, kind: str, name: str) -> dict:
    path = bench_dir / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def load_module(bench_dir: pathlib.Path, kind: str, name: str):
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                   f"{[w['name'] for w in bench['workloads']]})")


def metrics_of(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# -- the program under test --------------------------------------------------

def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=bool(cfg.get("qkv_bias")),
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


def build_engine(cfg: dict, params):
    """``ServeEngine`` over one HBM domain of ``kv_pool_pages`` pages."""
    from repro.core import topology
    from repro.placement.pool import BwapPagePool, MemoryDomain
    from repro.serve.engine import ServeEngine
    mcfg = model_config(cfg)
    pool = BwapPagePool(mcfg, [MemoryDomain(
        "hbm_local", cfg["kv_pool_pages"], topology.V5E_HBM_BW, True)],
        page_size=cfg["page_size"])
    eng = ServeEngine(mcfg, params, pool, max_batch=cfg["max_batch"])
    eng.scheduler.prefill_token_budget = cfg["prefill_token_budget"]
    return eng


class CompileCounter:
    """Counts XLA backend compiles, loads from the persistent cache
    included, while active."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self._COMPILE:
            self.compiles += 1
            self.seconds += secs

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


# -- the run record that metric readers read ---------------------------------

@dataclasses.dataclass
class Live:
    """One request the harness submitted."""
    req: object                     # the scheduler's Request
    due: float                      # host clock when it fell due
    submitted: float
    in_window: bool
    token_times: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Record:
    cfg: dict
    setup_s: float = 0.0
    t_open: float = 0.0
    t_close: float = 0.0
    live: list = dataclasses.field(default_factory=list)
    steps: int = 0
    step_s: float = 0.0             # host seconds inside ServeEngine.step
    step_times: list = dataclasses.field(default_factory=list)
    prefill_tokens: int = 0
    # one layer's attention launch of each step; each runs once per layer
    decode_launches: list = dataclasses.field(default_factory=list)
    prefill_launches: list = dataclasses.field(default_factory=list)
    model_flops: float = 0.0
    compiles: int = 0
    spans: spanlib.Spans | None = None
    trace: object = None            # tracereduce.Reduced
    peaks: roofline.Peaks | None = None
    failed: int = 0

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


# -- driving the engine ------------------------------------------------------

class Driver:
    """Submits requests and steps the engine, recording when each token of
    each request was produced (the end of the step that produced it)."""

    def __init__(self, eng, rec: Record, cfg: dict):
        self.eng, self.rec, self.cfg = eng, rec, cfg
        self.live: list[Live] = []
        self.in_window = False
        self.clients: dict[int, int] = {}     # closed loop: sid -> client
        self.pins: list = []                  # pages held for the trie
        self._plan = None
        sched = eng.scheduler
        schedule = sched.schedule

        def keep_plan():
            self._plan = plan = schedule()
            return plan
        sched.schedule = keep_plan

    def submit(self, r: traffic.Request, due: float) -> Live | None:
        try:
            self.eng.submit(r.prompt, max_new=r.max_new)
        except ValueError:
            self.rec.failed += 1
            return None
        req = self.eng.scheduler.queued[-1]
        lv = Live(req, due, time.perf_counter(), self.in_window)
        self.live.append(lv)
        return lv

    def busy(self) -> bool:
        return bool(self.eng.active or self.eng.waiting)

    def step(self) -> list[Live]:
        """One engine step, waited for; returns requests that finished."""
        eng = self.eng
        active = [lv for lv in self.live if not lv.req.done]
        before = [lv.req.produced for lv in active]
        t0 = time.perf_counter()
        eng.step()
        jax.block_until_ready((eng.view.k_pool, eng.view.v_pool))
        t1 = time.perf_counter()
        if self.in_window:
            self._count(t1 - t0)
        done = []
        for lv, n in zip(active, before):
            lv.token_times.extend([t1] * (lv.req.produced - n))
            if lv.req.done:
                done.append(lv)
        return done

    def _count(self, secs: float) -> None:
        """Work of the step just run, from its plan."""
        rec, cfg, plan = self.rec, self.cfg, self._plan
        rec.steps += 1
        rec.step_s += secs
        rec.step_times.append(secs)
        ps = cfg["page_size"]
        if plan.batch:
            ctx = [r.length + 1 for r in plan.batch]
            rec.decode_launches.append(roofline.decode_attention(cfg, ps, ctx))
            rec.model_flops += sum(roofline.token_flops(cfg, c) for c in ctx)
        chunks = [(lo, hi - lo) for _, lo, hi in plan.prefill_chunks
                  if hi > lo]
        if chunks:
            rec.prefill_launches.append(
                roofline.prefill_attention(cfg, ps, chunks))
            rec.prefill_tokens += sum(n for _, n in chunks)
            rec.model_flops += sum(roofline.chunk_flops(cfg, s, n)
                                   for s, n in chunks)


def fill(drv: Driver, trace: traffic.Trace, log=print) -> list:
    """Set-up that the traffic needs. Shared prefixes go into the prefix
    trie and stay pinned there, as a running deployment holds them; a
    closed loop serves until every client holds a first token. Returns the
    closed loop's per-client queues."""
    eng = drv.eng
    if trace.shared_prefixes:
        holders = [drv.submit(traffic.Request(p + [p[0]], 2), 0.0)
                   for p in trace.shared_prefixes]
        while not all(h.req.produced for h in holders):
            drv.step()
        for p in trace.shared_prefixes:      # pin: a hold of our own
            pages: list = []
            eng.view.probe_prefix(p + [p[0]], pages, count=False)
            drv.pins.append(pages)
        while drv.busy():
            drv.step()
    queues = []
    if trace.loop == "closed":
        queues = [[r for r in trace.requests if r.client == c]
                  for c in range(trace.clients)]
        # shortest prompt first: the order changes no request, and makes
        # the fill's shapes, and so the programs it compiles, the same for
        # every seed
        order = sorted(range(len(queues)),
                       key=lambda c: (len(queues[c][0].prompt), c))
        firsts = {c: drv.submit(queues[c].pop(0), time.perf_counter())
                  for c in order}
        t0, n = time.perf_counter(), 0
        while not all(f.req.produced for f in firsts.values()):
            drv.step()
            n += 1
            log(f"fill step {n}: {time.perf_counter() - t0:.1f} s, "
                f"{sum(1 for f in firsts.values() if f.req.produced)} "
                "clients with a first token")
        drv.clients = {f.req.sid: c for c, f in firsts.items()}
    return queues


def warm_decode(eng, cfg: dict, seconds: float, step_s: float):
    """Compile (or load) the decode programs that depend on the page-table
    width for the widths the window can reach: a decode batch of the
    running requests grows one page every ``page_size`` steps, and the
    window holds fewer than ``2 * seconds / step_s`` steps."""
    from repro.kernels.paged_attention import ops as paged_ops
    running = list(eng.scheduler.running)
    if not running:
        return 0
    b, ps = len(running), cfg["page_size"]
    steps = int(2 * seconds / max(step_s, 1e-3)) + 2
    lo = max(len(r.pages) for r in running)
    hi = max(-(-(r.length + steps + 1) // ps) for r in running)
    widths = range(lo, min(hi, lo + 64) + 1)
    cdt = jnp.dtype(cfg["torch_dtype"])
    q = jnp.zeros((b, cfg["num_attention_heads"], cfg["head_dim"]), cdt)
    kp, vp = eng.view.k_pool[0], eng.view.v_pool[0]
    lens = jnp.asarray(np.zeros(b, np.int32))
    for mp in widths:
        tables = jnp.asarray(np.zeros((b, mp), np.int32))
        pages = jnp.take_along_axis(tables, (lens // ps)[:, None], axis=1)
        out = paged_ops.paged_attention(q, kp, vp, tables, lens + 1,
                                        impl=eng.decoder.impl)
        jax.block_until_ready((pages, out))
    return len(widths)


def window(drv: Driver, trace: traffic.Trace, queues, rec: Record,
           seconds: float, sp: spanlib.Spans | None):
    """Open loop: each request is submitted when it falls due, between
    steps. Closed loop: a client sends its next request when its last one
    finishes."""
    eng = drv.eng
    span = sp.span if sp else (lambda name: _null())
    pending = list(trace.requests) if trace.loop == "open" else []
    rec.t_open = t0 = time.perf_counter()
    drv.in_window = True
    t_end = t0 + seconds
    with span("window"):
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            with span("submit"):
                while pending and t0 + pending[0].due_s <= now:
                    r = pending.pop(0)
                    drv.submit(r, t0 + r.due_s)
            if drv.busy():
                with span("step"):
                    done = drv.step()
                for lv in done:
                    c = drv.clients.get(lv.req.sid)
                    if c is not None and queues[c]:
                        nxt = drv.submit(queues[c].pop(0), time.perf_counter())
                        if nxt is not None:
                            drv.clients[nxt.req.sid] = c
            else:
                nxt = t0 + pending[0].due_s if pending else t_end
                with span("wait"):
                    time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
    rec.t_close = time.perf_counter()
    drv.in_window = False
    rec.live = drv.live


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# -- correctness -------------------------------------------------------------

def sample(live: list[Live], seed: int) -> list[Live]:
    """Requests whose served tokens the reference checks: the one with the
    most served tokens, then others drawn from the seed, finished ones
    first, until ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_REQUESTS``."""
    served = [lv for lv in live if lv.req.produced > 0]
    if not served:
        return []
    rng = np.random.default_rng(seed)
    fin = [lv for lv in served if lv.req.done]
    rest = [lv for lv in served if not lv.req.done]
    order = ([fin[i] for i in rng.permutation(len(fin))]
             + [rest[i] for i in rng.permutation(len(rest))])
    longest = max(served, key=lambda lv: lv.req.produced)
    out = [longest]
    n = longest.req.produced
    for lv in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_REQUESTS:
            break
        if lv is not longest:
            out.append(lv)
            n += lv.req.produced
    return out


def check(cfg: dict, ref, seqs, seed: int, limits: dict,
          control: bool = False) -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over the served tokens of ``seqs`` (token list,
    index of the first served token). With ``control`` the float8 control
    stands in the program's place: the number compared is the widest gap
    of the tokens it puts first at the same positions, and the program's
    own widest gap is kept under ``_program``."""
    w = weights.make(cfg, seed)
    gaps = [ref.logit_gaps(cfg, w, toks, first, with_control=control)
            for toks, first in seqs]
    widest = lambda key: float(max((g[key].max() for g in gaps),
                                   default=float("nan")))
    gap = widest("control" if control else "served")
    n = int(sum(len(g["served"]) for g in gaps))
    lim = limits["max_logit_gap"]["limit"]
    out = {"max_logit_gap": {"value": gap, "limit": lim},
           "served_tokens_checked": {"value": n, "limit": 1},
           "_ok": bool(gap <= lim and n >= 1)}
    if control:
        out["_program"] = widest("served")
    return out


# -- one run -----------------------------------------------------------------

def run(bench: dict, cell_name: str, seed: int, seconds: float, trace: bool,
        *, t_start: float, peaks: roofline.Peaks | None,
        bench_dir: pathlib.Path = BENCH_DIR, log=print,
        control: bool = False) -> dict:
    """Everything of one run but the look for a chip; returns the result
    line's object. With ``control`` the float8 control is judged in the
    program's place on the same sample (``control.py``), and the program's
    own widest gap is returned under the key ``program_gap``."""
    from repro.serve import engine as engine_mod
    cell = cell_of(bench, cell_name)
    cfg = load_json(bench_dir, "configs", cell["config"])
    mix = load_json(bench_dir, "traffic", cell["traffic"])
    limits = load_json(bench_dir, "limits", cell["name"])
    ref = load_module(bench_dir, "references", cfg["reference"])
    readers = {m["name"]: load_module(bench_dir, "metrics", m["name"])
               for m in metrics_of(bench, cell["name"], trace)}

    rec = Record(cfg, peaks=peaks)
    counter = CompileCounter()
    w = weights.make(cfg, seed)
    eng = build_engine(cfg, weights.program_params(w))
    del w
    tr = traffic.make_trace(mix, seed, cfg["vocab_size"], seconds)
    drv = Driver(eng, rec, cfg)
    queues = fill(drv, tr, log)
    if tr.loop == "closed":
        t = time.perf_counter()
        drv.step()
        warmed = warm_decode(eng, cfg, seconds, time.perf_counter() - t)
        log(f"warmed decode programs for {warmed} page-table widths")
    jax.block_until_ready((eng.view.k_pool, eng.view.v_pool))
    log(f"set-up: {counter.compiles} programs compiled or loaded, "
        f"{counter.seconds:.1f} s of it")
    sp = tracedir = None
    ops = engine_mod.paged_ops
    if trace:
        sp = spanlib.Spans()
        sp.wrap(eng.decoder, "forward_chunks", "forward_chunks", sync=True,
                sync_on=lambda: (eng.view.k_pool, eng.view.v_pool))
        sp.wrap(eng.decoder, "decode_step", "decode_step", sync=True)
        sp.wrap(eng.scheduler, "schedule", "schedule")
        engine_mod.paged_ops = spanlib.OpsProxy(ops, sp)
        tracedir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tracedir)
    rec.setup_s = time.perf_counter() - t_start
    before = counter.compiles
    try:
        window(drv, tr, queues, rec, seconds, sp)
    finally:
        rec.compiles = counter.compiles - before
        engine_mod.paged_ops = ops
        if trace:
            jax.profiler.stop_trace()
    counter.close()
    rec.spans = sp
    if trace:
        xp = sorted(pathlib.Path(tracedir).rglob("*.xplane.pb"))
        rec.trace = reduce_file(str(xp[-1]))
        shutil.rmtree(tracedir, ignore_errors=True)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if trace:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s

    picked = sample(rec.live, seed)
    seqs = [(list(lv.req.tokens), lv.req.prompt_len) for lv in picked]
    summary = describe(rec)
    # requests served in the window: submitted in it, or in flight into it
    attempted = rec.failed + sum(
        1 for lv in rec.live
        if lv.in_window or any(t >= rec.t_open for t in lv.token_times))
    # free the program's state before the reference runs
    del eng, drv, picked
    gc.collect()
    checks = check(cfg, ref, seqs, seed, limits, control)
    ok = checks.pop("_ok")
    program_gap = checks.pop("_program", None)

    metrics = {}
    for m in metrics_of(bench, cell["name"], trace):
        v = readers[m["name"]].read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for k, v in summary.items():
        log(f"{k}: {v}")
    out = {"correct": ok, "attempted": attempted, "failed": rec.failed,
           "metrics": metrics, "device": device}
    if trace:
        t = rec.trace
        out["breakdown"] = {"device_ops": t.top(t.module_s),
                            "idle_gaps": t.top(t.idle_by_span)}
    if control:
        out["program_gap"] = program_gap
    out["checks"] = checks
    return out


def describe(rec: Record) -> dict:
    """Counts and medians for the lines before the result."""
    win = [lv for lv in rec.live if lv.in_window]
    late = [lv.submitted - lv.due for lv in win]
    firsts = [lv.token_times[0] - lv.due for lv in win if lv.token_times]
    return {
        "window_s": rec.window_s, "steps": rec.steps,
        "step_s": rec.step_times,
        "requests_due_in_window": len(win),
        "requests_with_first_token": len(firsts),
        "requests_finished": sum(1 for lv in win if lv.req.done),
        "generator_late_s_max": max(late, default=0.0),
        "generator_late_s_median": (statistics.median(late) if late
                                    else 0.0),
        "ttft_s_median": statistics.median(firsts) if firsts else None,
        "compiles_in_window": rec.compiles,
    }
