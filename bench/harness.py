"""The benchmark's harness: build one cell through the program's builders,
warm it up, serve its traffic for a window, check what was served, and
reduce the result to the cell's metrics.

Everything that belongs to one configuration, traffic mix, metric or model
family lives in a file of its own, found by the names in ``BENCHMARK.json``
and in the configuration: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py`` and
``bench/models/<architecture>.py`` (``model["architectures"][0]``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

from bench import corpus as corpus_mod
from bench import models, reference, traffic
from bench import trace as tr

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# the traced slice of a --trace 1 run: the last TRACE_SECONDS of its window
TRACE_SECONDS = 6.0
# retrieval answers compared per run (a sample drawn from the seed)
RETRIEVAL_CHECKS = 48


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# finding a cell by name
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    config: dict  # bench/configs/<config>.json
    mix: dict  # bench/traffic/<traffic>.json
    chips: int
    end_to_end: list  # BENCHMARK.json entries that this cell reports
    per_layer: list
    family: object  # bench/models/<architecture>.py, as a module


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return True if "moves" not in metric else metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = traffic.load_mix(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    fam = models.family(config["model"], root / "bench" / "models")
    return Cell(name=name, config=config, mix=mix, chips=int(w["chips"]),
                end_to_end=e2e, per_layer=per_layer, family=fam)


def metric_reader(name: str, metrics_dir: Path = BENCH_DIR / "metrics"):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# building the system under test
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Built:
    corpus: object
    texts: list
    pipe: object
    cfg: object
    params: dict


def build(cell: Cell, seed: int, corpus_dir=None) -> Built:
    """Corpus (benchmark data), retrieval pipeline (program builders) and
    model weights (benchmark, on the device from ``seed``)."""
    import jax
    import jax.numpy as jnp

    from repro.core import PipelineConfig
    from repro.graph import CSRGraph, csr_to_ell
    from repro.launch import serve

    conf = cell.config
    t = time.perf_counter()
    c = corpus_mod.load(conf["corpus"], corpus_dir or corpus_mod.CACHE_DIR)
    texts = c.texts()
    say(f"setup: corpus {c.num_nodes} nodes, {len(c.src)} edges "
        f"({time.perf_counter() - t:.2f}s)")
    g = CSRGraph.from_edges(c.src, c.dst, c.num_nodes, symmetrize=True,
                            node_feat=c.feat, node_text=texts)
    ell = csr_to_ell(g)
    emb = jnp.asarray(c.feat)
    r, s = conf["retrieval"], conf["serving"]
    pcfg = PipelineConfig(
        strategy=r["strategy"], k_seeds=r["k_seeds"], max_hops=r["max_hops"],
        max_nodes=r["max_nodes"], filter_budget=r["filter_budget"],
        index_kind=r["index_kind"], retrieval_mode=r["retrieval_mode"],
        workset_cap=r["workset_cap"], max_prompt_len=s["prompt_cap"],
        node_token_budget=s["node_budget"])
    tok = serve.graph_tokenizer(g, max_len=s["prompt_cap"],
                                node_budget=s["node_budget"])
    pipe = serve.build_rag_pipeline(g, ell, emb, pcfg, tok)
    cfg = cell.family.program_config(conf["model"], conf["name"])
    if tok.vocab.size > cfg.vocab:
        raise ValueError(f"tokenizer ids reach {tok.vocab.size - 1}, past "
                         f"the vocabulary of {cfg.vocab}")
    t = time.perf_counter()
    params = cell.family.make_params(conf["model"], seed)
    jax.block_until_ready(params)
    say(f"setup: weights {sum(x.size for x in jax.tree.leaves(params))} "
        f"parameters ({time.perf_counter() - t:.2f}s)")
    return Built(corpus=c, texts=texts, pipe=pipe, cfg=cfg, params=params)


def make_engine(cell: Cell, b: Built, max_new: int):
    """The engine under test, its KV arena sized by ``arena_len`` for the
    longest output the traffic asks for."""
    from repro.launch import serve
    from repro.serving import RAGServeEngine, ServingConfig

    s = cell.config["serving"]
    conf = ServingConfig(slots=s["slots"],
                         cache_len=serve.arena_len(b.cfg, b.pipe.tokenizer,
                                                   max_new),
                         degraded_mode=s["degraded_mode"],
                         max_retries=s["max_retries"])
    return RAGServeEngine(b.pipe, b.params, b.cfg, config=conf)


# --------------------------------------------------------------------------
# serving and recording
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Rec:
    uid: int
    spec: object
    req: object
    due: float  # absolute host clock
    in_window: bool
    submitted: float = math.nan
    prompt_at: float = math.nan
    first_at: float = math.nan
    done_at: float = math.nan
    n_tokens: int = 0
    ok: bool = False


class Recorder:
    """Per-request stamps on the host clock, and the useful work served,
    counted from the tokens each request gains at every engine step."""

    def __init__(self, eng, cell: Cell, texts: list):
        self.eng = eng
        self.model = cell.config["model"]
        self.family = cell.family
        self.texts = texts
        self.live: dict = {}  # uid -> Rec, submitted and not yet returned
        self.done: list = []
        self.flops = 0.0
        self.prefills = 0
        self.prompt_tokens = 0

    def submit(self, spec, due: float, in_window: bool) -> Rec:
        from repro.serving import RAGRequest

        text = " ".join(self.texts[spec.node].split()[:4])
        req = RAGRequest(uid=spec.uid, query_emb=spec.query, query_text=text,
                         max_new_tokens=spec.max_new)
        rec = Rec(uid=spec.uid, spec=spec, req=req, due=due,
                  in_window=in_window)
        rec.submitted = time.perf_counter()
        self.live[spec.uid] = rec
        self.eng.submit(req)
        return rec

    def _gain(self, rec: Rec, n: int, now: float) -> None:
        if n <= rec.n_tokens:
            return
        plen = len(rec.req.prompt_ids)
        if rec.n_tokens == 0:
            rec.first_at = now
            self.flops += self.family.prefill_flops(self.model, plen)
            self.prefills += 1
            self.prompt_tokens += plen
        for t in range(max(rec.n_tokens, 1), n):
            self.flops += self.family.decode_flops(self.model,
                                                    plen + t - 1)
        rec.n_tokens = n

    def step(self) -> list:
        """One engine step; returns the records that came back."""
        finished = self.eng.step()
        now = time.perf_counter()
        for inner in self.eng.engine.active:
            if inner is not None and inner.uid in self.live:
                self._gain(self.live[inner.uid], len(inner.out_tokens), now)
        for rec in self.live.values():
            if math.isnan(rec.prompt_at) and rec.req.prompt_ids is not None:
                rec.prompt_at = now
        out = []
        for r in finished:
            rec = self.live.pop(r.uid)
            self._gain(rec, len(r.out_tokens), now)
            rec.done_at = now
            rec.ok = bool(r.done and not (r.failed or r.degraded or r.stale
                                          or r.shed or r.truncated)
                          and len(r.out_tokens) == r.max_new_tokens)
            self.done.append(rec)
            out.append(rec)
        return out


def wrap_spans(eng) -> None:
    """Host spans around the harness's calls into each layer (traced runs
    only): they name what the host was doing in each device-idle gap."""
    import jax

    def span(obj, attr, label):
        fn = getattr(obj, attr, None)
        if fn is None:
            return

        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(tr.HOST_PREFIX + label):
                return fn(*a, **k)

        setattr(obj, attr, wrapped)

    span(eng.pipeline, "retrieve_many", "retrieve_many")
    span(eng.prefetcher, "collect", "retrieval_collect")
    span(eng.pipeline.tokenizer, "linearize", "linearize")
    span(eng.engine, "step", "engine_step")


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    tokens: int
    late_s: list  # open loop: how late each request was submitted
    compiles: int
    trace: object = None
    trace_lo: float = math.nan  # host clock of the traced slice
    trace_hi: float = math.nan
    counters: dict = dataclasses.field(default_factory=dict)


class _CompileCounter:
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.n += 1


def _snapshot(rec: Recorder) -> dict:
    e = rec.eng
    return {"retrieved_queries": e.retrieved_queries,
            "retrieval_batches": e.retrieval_batches,
            "decode_steps": e.engine.decode_steps,
            "emitted_tokens": e.engine.emitted_tokens,
            "prefills": rec.prefills, "prompt_tokens": rec.prompt_tokens,
            "useful_flops": rec.flops, "cache_hits": e.cache_hits,
            "cache_misses": e.cache_misses}


def serve_window(rec: Recorder, stream, mix: dict, seconds: float,
                 trace_slice: float = 0.0, counter=None) -> Window:
    """Serve ``mix`` for ``seconds``; with ``trace_slice`` > 0 the profiler
    traces the last ``trace_slice`` seconds.  Requests due in the window
    are then followed to completion (the drain)."""
    import jax

    eng = rec.eng
    open_loop = mix["loop"] == "open"
    late = []
    tracing = None
    snap0 = None
    t0 = time.perf_counter()
    tok0 = eng.engine.emitted_tokens
    if counter is not None:
        counter.on = True
    if open_loop:
        nxt = stream.next()
    else:
        for _ in range(int(mix["clients"])):
            rec.submit(stream.next(), due=t0, in_window=True)
    win = Window(t0=t0, t1=t0, tokens=0, late_s=late, compiles=0)
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        if trace_slice and tracing is None and \
                now - t0 >= seconds - trace_slice:
            jax.profiler.start_trace(str(tr.TRACE_DIR))
            tracing = jax.profiler.TraceAnnotation(tr.HOST_PREFIX + "window")
            tracing.__enter__()
            win.trace_lo = time.perf_counter()
            snap0 = _snapshot(rec)
        if open_loop:
            while t0 + nxt.due <= now:
                late.append(now - (t0 + nxt.due))
                rec.submit(nxt, due=t0 + nxt.due, in_window=True)
                nxt = stream.next()
            if not rec.live:
                wait = min(t0 + nxt.due, t0 + seconds) - now
                if wait > 0:
                    with jax.profiler.TraceAnnotation(
                            tr.HOST_PREFIX + "await_arrival"):
                        time.sleep(wait)
                continue
        with jax.profiler.TraceAnnotation(tr.HOST_PREFIX + "rag_step"):
            back = rec.step()
        if not open_loop:
            t_back = time.perf_counter()
            for _ in back:
                if t_back - t0 < seconds:
                    rec.submit(stream.next(), due=t_back, in_window=True)
    win.t1 = time.perf_counter()
    win.tokens = eng.engine.emitted_tokens - tok0
    if counter is not None:
        counter.on = False
        win.compiles = counter.n
    if tracing is not None:
        tracing.__exit__(None, None, None)
        win.trace_hi = time.perf_counter()
        snap1 = _snapshot(rec)
        win.counters = {k: snap1[k] - snap0[k] for k in snap0}
    while rec.live:  # the drain: latencies only, no rate
        rec.step()
    if tracing is not None:
        jax.profiler.stop_trace()
        win.trace = tr.load()
    return win


def warm_up(rec: Recorder, stream, mix: dict) -> None:
    """Serve unmeasured requests of the cell's own traffic to completion:
    every program the window runs is compiled or loaded here."""
    cap = mix.get("warmup_tokens")
    for spec in stream.take(int(mix["warmup_requests"])):
        if cap:
            spec.max_new = min(spec.max_new, int(cap))
        rec.submit(spec, due=time.perf_counter(), in_window=False)
    while rec.live:
        rec.step()
    rec.done.clear()


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------
def pad_len(conf: dict, max_new: int) -> int:
    return -(-(int(conf["serving"]["prompt_cap"]) + max_new) // 128) * 128


def sample_checks(done: list, seed: int, check_tokens: int):
    """(LM sample, retrieval sample) of the window's served requests, drawn
    from the seed; the LM sample holds the longest request."""
    ok = [r for r in done if r.in_window and r.ok]
    rng = np.random.default_rng([seed, 7])
    order = list(rng.permutation(len(ok)))
    if not ok:
        return [], []
    longest = max(range(len(ok)), key=lambda i: ok[i].n_tokens)
    order.remove(longest)
    lm, toks = [ok[longest]], ok[longest].n_tokens
    for i in order:
        if toks >= check_tokens:
            break
        lm.append(ok[i])
        toks += ok[i].n_tokens
    ret = [ok[i] for i in ([longest] + order)[:RETRIEVAL_CHECKS]]
    return lm, ret


def check_retrieval(cell: Cell, b: Built, recs: list) -> tuple:
    ref = reference.RetrievalReference(
        b.corpus, b.texts, cell.config["retrieval"], cell.config["serving"],
        cell.config["correct"]["tie_tol"])
    faults = []
    for r in recs:
        f = ref.faults(r.spec.query, r.req.query_text, r.req.retrieved_nodes,
                       r.req.prompt_ids)
        faults += [f"request {r.uid}: {x}" for x in f]
    return len(faults), faults


def lm_gaps(cell: Cell, params, recs: list, max_new: int,
            control: bool = False):
    """Widest served-token gap below the reference's best (and the
    control's, when asked) over the sampled requests."""
    hp = cell.family.hparams(cell.config["model"])
    pad = pad_len(cell.config, max_new)
    worst, worst_c, n = 0.0, 0.0, 0
    for r in recs:
        g, gc_ = reference.request_gaps(cell.family, params, hp,
                                        r.req.prompt_ids,
                                        r.req.out_tokens, pad,
                                        quant_control=control)
        worst = max(worst, float(g.max()))
        n += len(g)
        if gc_ is not None:
            worst_c = max(worst_c, float(gc_.max()))
    return worst, worst_c, n


# --------------------------------------------------------------------------
# what a metric reader sees
# --------------------------------------------------------------------------
@dataclasses.dataclass
class RunView:
    cell: Cell
    recs: list  # records of the requests due in the window
    window_s: float
    tokens: int  # tokens emitted in the window
    setup_s: float
    peaks: dict
    win: Window

    @property
    def loop(self) -> str:
        return self.cell.mix["loop"]

    @property
    def trace(self):
        return self.win.trace

    @property
    def trace_window(self):
        """(lo, hi) of the traced slice on the trace's clock, in ns."""
        return tr.window(self.win.trace)

    @property
    def counters(self) -> dict:
        """Program counters and served work over the traced slice."""
        return self.win.counters

    def traced_recs(self) -> list:
        """Records of the requests due inside the traced slice."""
        lo, hi = self.win.trace_lo, self.win.trace_hi
        return [r for r in self.recs if lo <= r.due < hi]


def quantile(values, q: float) -> float:
    """Nearest-rank quantile; infinite values (unserved requests) count."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[max(0, math.ceil(q * len(v)) - 1)])
