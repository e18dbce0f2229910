"""Tenants ask questions about shared documents: an open loop of requests,
each a new session on one document of a pool, served by the program's
``SessionManager`` over its shared segment store.

Each request opens a session on its document, asks for the whole document
as its prompt and a greedy answer of its own length, and closes when the
answer drains.  The pool's document lengths, which document holds which
popularity rank, and so every compiled shape are the traffic's layout,
drawn from its own ``layout_seed``; the seed makes the documents' tokens,
and the weights; the schedule of arrivals, documents and answer lengths
is one trace drawn from the layout seed (``arrivals.schedule``), replayed
for every seed, so every seed offers the same work.

Set-up makes the weights on the device in one jitted call, then warms the
program: one request on each document of the pool, least popular first
(every prompt shape, and a store left as steady traffic leaves it: the
head of the pool resident, and every document's put and hit counts, which
the store's retention scores read), one pass of ``max_batch`` requests
whose answers end one step apart (every decode batch size and every pack
split), and an open-loop lead-in at the cell's rate, long enough for the
store's evictions per request to level off.  The window then offers the
requests due in ``--seconds`` at that rate; time to first token counts
from when a request was due, each gap between tokens as the client sees
it after a scheduler round.  The window's requests drain after it; one
still open ``drain_s`` after the window closed has failed.

``correct``: a sample of the window's requests drawn from the seed, with
the longest answer among them, is run through the plain float32 reference
(``reference/serve.py``) once the program's state is freed.  Compared: the
widest gap by which a served token's logit lies below the reference's best
at its position, and the worst relative distance of the served logit rows
(first token, decode positions 1, n/2 and the last) from the reference's.
The window's first request whose plan computes its document's first chunk
alone before a stored segment (:func:`cold_first_chunk`) is compared too,
sampled or not.
"""
# no ``from __future__ import annotations``: the harness loads drivers by
# file name, outside ``sys.modules``, where dataclasses cannot resolve
# string annotations
import dataclasses
import gc
import json
import sys
import time
from dataclasses import dataclass, field

import numpy as np

import arrivals
import harness
import traffic
from reference import serve as ref
from work import serve as work

#: salts of the schedules and draws, one per use
SALT_TOKENS, SALT_LEAD, SALT_WINDOW, SALT_SAMPLE = 21, 23, 24, 25


# -- the program's configuration and weights ----------------------------------
def arch(cfg: dict):
    """The program's ``ArchConfig`` for this configuration file."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(cfg["program_arch"]), name=cfg["name"],
        n_layers=int(cfg["num_hidden_layers"]), d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]), head_dim=int(cfg["head_dim"]),
        d_ff=int(cfg["intermediate_size"]), vocab_size=int(cfg["vocab_size"]),
        norm_eps=float(cfg["rms_norm_eps"]), rope_theta=float(cfg["rope_theta"]),
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


def make_params(seed: int, cfg: dict):
    """The program's parameter tree, drawn on the device in one jitted call
    with the reference's generator: layer ``i`` of every stacked leaf is
    ``reference.serve.layer_weights(key, cfg, i)``."""
    import jax
    import jax.numpy as jnp

    n = ref.dims(cfg)

    def build(key):         # the key is an operand: one program for every seed
        st = jax.vmap(lambda i: ref.layer_weights(key, cfg, i))(jnp.arange(n["L"]))
        layer = {"ln1": st["ln1"], "ln2": st["ln2"],
                 "mixer": {k: st[k] for k in ("wq", "wk", "wv", "wo")},
                 "mlp": {k: st[k] for k in ("w_gate", "w_up", "w_down")}}
        return {"embed": ref.embed_weights(key, cfg),
                "final_norm": jnp.ones((n["d"],), jnp.bfloat16),
                "segments": [{"p0": layer}],
                "lm_head": ref.head_weights(key, cfg)}

    return jax.block_until_ready(jax.jit(build)(ref.root_key(seed)))


# -- the pool ---------------------------------------------------------------
def pool_lengths(tr: dict) -> np.ndarray:
    """Document length of each popularity rank, the same for every seed:
    lengths at fixed quantiles, in whole pages, ranks drawn from the
    layout seed."""
    page = int(tr["page_tokens"])
    lens = traffic.sizes(tr["doc_len"], int(tr["docs"]))
    lens = np.maximum((lens + page // 2) // page * page, page)
    rng = np.random.default_rng(traffic.seed32(int(tr["layout_seed"]), 1))
    return rng.permutation(lens)


def pool_docs(tr: dict, vocab: int, seed: int) -> list:
    lens = pool_lengths(tr)
    rng = np.random.default_rng(traffic.seed32(seed, SALT_TOKENS))
    return [rng.integers(1, vocab, int(n), dtype=np.int32) for n in lens]


# -- one request --------------------------------------------------------------
@dataclass
class Request:
    due: float              # seconds after its phase's start
    doc: int                # popularity rank
    n_new: int
    sid: int = -1
    submitted: float = 0.0
    due_at: float = 0.0     # when it was due, on the host clock
    gaps: list = field(default_factory=list)    # plan's uncovered ranges
    times: list = field(default_factory=list)   # when each token was seen
    tokens: list = field(default_factory=list)
    reused: int = 0
    computed: int = 0
    cold_first: bool = False    # see cold_first_chunk
    window: bool = False        # due inside the measured window
    done: bool = False
    #: token index -> served logits (numpy, or a device array until read)
    logits: dict = field(default_factory=dict)
    want: tuple = ()        # token indices whose logits are kept


class Server:
    """The program under test and the client side that drives it."""

    def __init__(self, seed: int, cell: dict, tr: dict, cfg: dict, compiles):
        from repro.models.lm import LM
        from repro.serve.kv_cache import SegmentStore
        from repro.serve.session import SessionManager

        self.cell, self.tr, self.cfg = cell, tr, cfg
        self.n = ref.dims(cfg)
        self.compiles = compiles
        self.docs = pool_docs(tr, self.n["V"], seed)
        t = time.perf_counter()
        self.params = make_params(seed, cfg)
        print(f"set-up: weights in {time.perf_counter() - t:.1f} s",
              file=sys.stderr, flush=True)
        prog = cell["program"]
        store = SegmentStore(byte_budget=int(prog["byte_budget"]),
                             seq_bucket=int(prog["segment_bucket"]))
        self.mgr = SessionManager(
            LM(arch(cfg)), self.params, store=store,
            chunk_tokens=int(prog["chunk_tokens"]),
            decode_bucket=int(prog["slot_tokens"]),
            max_batch=int(prog["max_batch"]),
            decode_materialize=bool(prog["decode_materialize"]))
        self.late: list = []
        self.doc_ids: dict = {}     # popularity rank -> the program's doc id
        self.cold_first = 0         # plans counted by cold_first_chunk
        self.witness = None         # the window's first such request

    def counters(self) -> dict:
        sc = self.mgr.sched
        return {"decode_calls": sc.decode_calls, "decode_rows": sc.decode_rows,
                "decode_valid_tokens": sc.decode_valid_tokens,
                "pack_rebuilds": sc.pack_rebuilds,
                "evictions": self.mgr.store.evictions,
                "cold_first_chunk_plans": self.cold_first}

    def _submit(self, r: Request, now: float, due_at: float) -> None:
        with harness.span("cb.submit"):
            doc = self.docs[r.doc]
            r.sid = self.mgr.add_session(doc)
            self.doc_ids[r.doc] = self.mgr.sessions[r.sid].doc_id
            plan = self.mgr.submit(r.sid, len(doc), r.n_new, greedy=True)
        r.submitted, r.due_at = now, due_at
        r.gaps = [(s.rng.lo, s.rng.hi) for s in plan.steps if s.model_id is None]
        r.cold_first = cold_first_chunk(plan.steps,
                                        int(self.cell["program"]["chunk_tokens"]))
        self.cold_first += r.cold_first
        if r.cold_first and r.window and self.witness is None:
            keep_rows(r)
            self.witness = r
        if 0 in r.want:
            r.logits[0] = self.mgr.sessions[r.sid].logits

    def _collect(self, live: dict, t: float) -> None:
        for sid, r in list(live.items()):
            s = self.mgr.sessions[sid]
            while len(r.times) < len(s.out_tokens):
                r.times.append(t)
            k = len(s.out_tokens)
            if s.remaining > 0 and s.ticket is None and k in r.want \
                    and k not in r.logits:
                r.logits[k] = np.array(s.logits[0], np.float32)
            if s.remaining == 0 and s.ticket is None:
                r.tokens = list(s.out_tokens)
                r.reused, r.computed = s.stats.tokens_reused, s.stats.tokens_computed
                r.done = True
                self.mgr.close_session(sid)
                del live[sid]

    def serve(self, reqs: list, t0: float, *, deadline: float = float("inf"),
              max_live: int = 0, events: tuple = ()) -> None:
        """Offer ``reqs`` (due ``t0 + r.due``, or back to back with at most
        ``max_live`` open when that is set) and step the scheduler until
        every one has drained or ``deadline`` passes.  ``events`` are
        ``(time, fn)``, each called once when the clock passes its time; a
        number it returns is the new deadline."""
        pending = list(reversed(reqs))
        events = sorted(events, key=lambda e: e[0], reverse=True)
        live: dict = {}
        while pending or live:
            now = time.perf_counter()
            while events and now >= events[-1][0]:
                moved = events.pop()[1]()
                deadline = deadline if moved is None else moved
                now = time.perf_counter()
            if now >= deadline:
                break
            while pending and (len(live) < max_live if max_live
                               else t0 + pending[-1].due <= now):
                r = pending.pop()
                due_at = now if max_live else t0 + r.due
                self.late.append(now - due_at)
                self._submit(r, now, due_at)
                live[r.sid] = r
                now = time.perf_counter()
            if live:
                with harness.span("cb.step"):
                    self.mgr.step()
                self._collect(live, time.perf_counter())
            elif pending:
                wake = t0 + pending[-1].due
                if events:
                    wake = min(wake, events[-1][0])
                time.sleep(max(0.0, wake - time.perf_counter()))
        for _, fn in reversed(events):
            fn()
        for sid in list(live):
            self.mgr.close_session(sid)


def cold_first_chunk(steps, chunk: int) -> bool:
    """A plan that computes its document's first chunk, and nothing more,
    before a stored segment: what a store leaves once it has evicted a
    first chunk alone, the program's cold-start path followed by an insert."""
    steps = sorted(steps, key=lambda s: s.rng.lo)
    return (len(steps) > 1 and steps[0].model_id is None
            and steps[0].rng.lo == 0 and steps[0].rng.hi <= chunk)


def requests(sched: list) -> list:
    return [Request(due, rank, n) for due, rank, n in sched]


def warm(srv: Server, max_batch: int) -> dict:
    """Set-up's passes over the program (module docstring): seconds each."""
    out = {}
    t = time.perf_counter()
    # every document once, least popular first: every prompt shape, and the
    # store holding what steady traffic keeps
    srv.serve([Request(0.0, r, 1) for r in range(len(srv.docs) - 1, -1, -1)],
              0.0, max_live=max_batch)
    out["pool_pass_s"] = time.perf_counter() - t
    # every decode batch size and pack split: prompts built from the store,
    # answers long enough that all are decoding together before the first
    # ends, and ending one step apart
    t = time.perf_counter()
    srv.serve([Request(0.0, i, max_batch + 2 + i) for i in range(max_batch)],
              0.0, max_live=max_batch)
    out["batch_pass_s"] = time.perf_counter() - t
    return out


def offered(tr: dict, rate: float, seconds: float) -> tuple[list, list]:
    """The lead-in's requests (due before the window opens at 0) and the
    window's."""
    lead_s = float(tr["lead_in_s"])
    lead = requests(arrivals.schedule(tr, rate, lead_s, SALT_LEAD))
    for r in lead:
        r.due -= lead_s
    reqs = requests(arrivals.schedule(tr, rate, seconds, SALT_WINDOW))
    for r in reqs:
        r.window = True
    return lead, reqs


def sample(reqs: list, seed: int, k: int) -> list:
    """``k`` requests drawn from the seed, with the longest answer among
    them; each keeps its served logits at tokens 0, 1, n/2 and n-1."""
    rng = np.random.default_rng(traffic.seed32(seed, SALT_SAMPLE))
    longest = max(range(len(reqs)), key=lambda i: (reqs[i].n_new, -i))
    rest = [i for i in rng.permutation(len(reqs)) if i != longest][:max(k - 1, 0)]
    picks = [reqs[i] for i in sorted([longest, *rest])]
    for r in picks:
        keep_rows(r)
    return picks


def keep_rows(r: Request) -> None:
    """Keep ``r``'s served logits at tokens 0, 1, n/2 and n-1."""
    r.want = tuple(sorted({0, 1, r.n_new // 2, r.n_new - 1}))


def compared(srv: Server, picks: list) -> list:
    """The requests ``correct`` compares, each with its kept logit rows:
    the sample, and the window's first plan that computed a first chunk
    alone before a stored segment, where there is one."""
    w = srv.witness
    rs = picks + [w] if w is not None and all(r is not w for r in picks) else picks
    return [(r, {k: np.asarray(v, np.float32).reshape(-1)
                 for k, v in r.logits.items()}) for r in rs]


def pct(xs, q: float) -> float:
    """The q-th percentile of ``xs`` (numpy's linear interpolation)."""
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else float("inf")


def run(run: harness.Run, trace_dir) -> None:
    cell, tr, cfg = harness.cell_spec(run)
    import jax

    srv = Server(run.seed, cell, tr, cfg, run.notes["compiles"])
    print(f"set-up: weights and program in "
          f"{time.perf_counter() - run.notes['t_start']:.1f} s",
          file=sys.stderr, flush=True)
    info = warm(srv, int(cell["program"]["max_batch"]))
    c = srv.compiles.snapshot()
    print(f"set-up: {json.dumps(info)}; {c[0]} programs built ({c[1]} "
          f"compiled, {srv.compiles.compile_s:.1f} s)", file=sys.stderr, flush=True)
    lead, reqs = offered(tr, float(cell["rate_per_s"]), run.seconds)
    picks = sample(reqs, run.seed, int(cell["check"]["sample"]))
    gc.collect()
    gc.freeze()
    window = harness.Window(run.seconds, trace_dir)
    marks = {}

    def open_():
        run.setup_s = time.perf_counter() - run.notes["t_start"]
        srv.late.clear()
        marks["open"] = (srv.counters(), srv.compiles.snapshot())
        jax.config.update("jax_log_compiles", True)    # names any window compile
        marks["t0"] = window.open()

    def close():
        window.close()
        jax.config.update("jax_log_compiles", False)
        marks["close"] = (srv.counters(), srv.compiles.snapshot())
        # the drain's allowance starts once the trace is written
        marks["deadline"] = time.perf_counter() + float(tr["drain_s"])
        return marks["deadline"]

    t_lead = time.perf_counter()
    t0 = t_lead + float(tr["lead_in_s"])
    srv.serve(lead + reqs, t0, deadline=t0 + run.seconds + float(tr["drain_s"]),
              events=((t0, open_), (t0 + run.seconds, close)))
    run.window_s = window.t1 - window.t0
    run.memory_peak_bytes = (run.device.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)
    measure(run, srv, reqs, marks, cell, tr)
    captured = compared(srv, picks)
    docs = srv.docs
    del srv, lead, reqs
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()
    run.checks = check(run.seed, cfg, cell, docs, captured)["checks"]


def measure(run, srv: Server, reqs: list, marks: dict, cell: dict, tr: dict) -> None:
    """End-to-end metrics of the window's requests, and the counters and
    work the per-layer readers read.  A request that never drained counts
    as waiting until the drain limit, and misses both limits."""
    lim = cell["limits"]
    n = srv.n
    end = marks["deadline"]
    ttft, gaps, met = [], [], 0
    reused = computed = 0
    for r in reqs:
        if not r.done:
            ttft.append((end - r.due_at) * 1e3)
            continue
        first = (r.times[0] - r.due_at) * 1e3
        ttft.append(first)
        g = [(b - a) * 1e3 for a, b in zip(r.times, r.times[1:])]
        gaps.extend(g)
        if first <= lim["ttft_ms"] and (not g or np.mean(g) <= lim["tpot_ms"]):
            met += 1
        reused += r.reused
        computed += r.computed
    run.attempted = len(reqs)
    run.failed = sum(not r.done for r in reqs)
    # the tails the cell names, e.g. {"ttft_p85_ms": ["ttft", 85]}
    samples = {"ttft": ttft, "itl": gaps}
    run.end_to_end = {name: (pct(samples[kind], q), "ms")
                      for name, (kind, q) in cell["tails"].items()}
    run.end_to_end["setup_s"] = (run.setup_s, "s")
    (c0, k0), (c1, k1) = marks["open"], marks["close"]
    d = {k: c1[k] - c0[k] for k in c0}
    # work dispatched while the window was open
    t_close = marks["t0"] + run.window_s
    ext = [0.0, 0.0]
    prefill_ops = 0.0
    for r in reqs:
        if r.sid < 0 or r.submitted > t_close:
            continue
        for kind, start, nb in work.build_calls(r.gaps, len(srv.docs[r.doc]),
                                                int(cell["program"]["chunk_tokens"])):
            a = work.extend_attention(n, start, nb)
            if kind == "extend":
                ext[0] += a[0]
                ext[1] += a[1]
            prefill_ops += a[0] + nb * work.matmul_flops_per_token(n)
        prefill_ops += work.head_flops(n)
    dec = work.decode_attention(n, d["decode_valid_tokens"], d["decode_rows"])
    decode_ops = dec[0] + d["decode_rows"] * (work.matmul_flops_per_token(n) +
                                              work.head_flops(n))
    run.work = {"extend_attention": tuple(ext), "decode_attention": dec,
                "step_ops": (prefill_ops + decode_ops, 0.0)}
    run.counters.update(
        tokens_reused=reused, tokens_computed=computed,
        decode_calls=d["decode_calls"], decode_rows=d["decode_rows"],
        pack_rebuilds=d["pack_rebuilds"], evictions=d["evictions"],
        cold_first_chunk_plans=d["cold_first_chunk_plans"],
        setup_cold_first_chunk_plans=c0["cold_first_chunk_plans"],
        setup_programs=k0[0], setup_compiles=k0[1],
        window_programs=k1[0] - k0[0], window_compiles=k1[1] - k0[1],
        met_share=met / max(len(reqs), 1),
        ttft_p50_ms=pct(ttft, 50), itl_p50_ms=pct(gaps, 50),
        late_p50_ms=pct(srv.late, 50) * 1e3 if srv.late else 0.0,
        late_max_ms=max(srv.late) * 1e3 if srv.late else 0.0,
        tokens_served=sum(len(r.tokens) for r in reqs))
    print(f"window: {len(reqs)} requests due, {run.failed} failed; "
          f"{json.dumps(run.counters)}", file=sys.stderr, flush=True)


# -- correct ------------------------------------------------------------------
def reference_inputs(docs: list, captured: list, slot: int):
    """Teacher-forced rows (prompt + served tokens but the last), padded to
    ``slot`` positions, and the positions whose logits predict each served
    token."""
    seqs = np.zeros((len(captured), slot), np.int32)
    want = []
    for j, (r, _) in enumerate(captured):
        doc = docs[r.doc]
        row = np.concatenate([doc, np.asarray(r.tokens[:-1], np.int32)])
        seqs[j, :len(row)] = row
        want.append(list(range(len(doc) - 1, len(doc) - 1 + len(r.tokens))))
    return seqs, want


def readings(ref_logits: list, captured: list, *, tokens_of=None) -> dict:
    """The two numbers compared: the widest gap (reference's best logit
    minus its logit of the served token, or of ``tokens_of``'s token), and
    the worst relative L2 distance of the kept logit rows."""
    gap, rel = 0.0, 0.0
    for j, (r, kept) in enumerate(captured):
        lg = ref_logits[j]
        toks = r.tokens if tokens_of is None else tokens_of[j]
        for i, tok in enumerate(toks):
            gap = max(gap, float(lg[i].max() - lg[i][tok]))
        for k, row in kept.items():
            rel = max(rel, float(np.linalg.norm(row - lg[k]) /
                                 np.linalg.norm(lg[k])))
    return {"token_gap": gap, "logit_rel_l2": rel}


def check(seed: int, cfg: dict, cell: dict, docs: list, captured: list,
          control: bool = False) -> dict:
    """The checks beside their limits; with ``control`` also the control's
    checks against the same limits: the reference with every matrix product's operands in
    per-tensor scaled float8_e4m3fn, read at the same positions (its gap is
    that of the token it puts first)."""
    chk = cell["check"]
    lim = chk["limits"]
    if not captured or any(not r.done or len(r.tokens) != r.n_new
                           or len(kept) != len(r.want) for r, kept in captured):
        failed = [harness.Check(k, float("inf"), float(v)) for k, v in lim.items()]
        return {"checks": failed, "control": failed, "reference_s": 0.0}
    seqs, want = reference_inputs(docs, captured, int(cell["program"]["slot_tokens"]))
    t = time.perf_counter()
    got = ref.forward_logits(seed, cfg, seqs, want, q_block=int(chk["q_block"]))
    vals = readings(got, captured)
    out = {"checks": [harness.Check(k, vals[k], float(lim[k])) for k in lim],
           "reference_s": time.perf_counter() - t}
    if control:
        low = ref.forward_logits(seed, cfg, seqs, want, q_block=int(chk["q_block"]),
                                 fp8=True)
        firsts = [lg.argmax(-1) for lg in low]
        ctrl = [(r, {k: low[j][k] for k in kept})
                for j, (r, kept) in enumerate(captured)]
        vals = readings(got, ctrl, tokens_of=firsts)
        out["control"] = [harness.Check(k, vals[k], float(lim[k])) for k in lim]
    return out


def sweep(run: harness.Run) -> int:
    """One set-up, then a window at each of the cell's ``sweep_rates``, in
    order, the store carried from one to the next: per rate, one JSON line
    with the share of requests that met both limits, the tails, failures
    and the requests still open when the window closed (the backlog)."""
    cell, tr, cfg = harness.cell_spec(run)
    srv = Server(run.seed, cell, tr, cfg, run.notes["compiles"])
    warm(srv, int(cell["program"]["max_batch"]))
    for rate in cell["sweep_rates"]:
        lead, reqs = offered(tr, float(rate), run.seconds)
        srv.late.clear()
        marks: dict = {}

        def open_():
            srv.late.clear()
            marks["open"] = (srv.counters(), srv.compiles.snapshot())

        def close():
            marks["close"] = (srv.counters(), srv.compiles.snapshot())
            marks["backlog"] = sum(1 for r in reqs if r.sid >= 0 and not r.done)
            marks["deadline"] = time.perf_counter() + float(tr["drain_s"])

        t0 = marks["t0"] = time.perf_counter() + float(tr["lead_in_s"])
        try:
            srv.serve(lead + reqs, t0, deadline=t0 + run.seconds + float(tr["drain_s"]),
                      events=((t0, open_), (t0 + run.seconds, close)))
        except Exception as e:      # out of device memory: the program's limit
            print(json.dumps({"rate_per_s": rate, "error": str(e)[:300]}), flush=True)
            return 0
        run.window_s, run.setup_s = run.seconds, 0.0
        measure(run, srv, reqs, marks, cell, tr)
        print(json.dumps({"rate_per_s": rate, "requests": len(reqs),
                          "failed": run.failed, "backlog_at_close": marks["backlog"],
                          **{k: v[0] for k, v in run.end_to_end.items() if k != "setup_s"},
                          **run.counters,
                          "memory_peak_bytes": (run.device.memory_stats() or {}).get(
                              "peak_bytes_in_use", 0)}), flush=True)
    return 0


def tool(run: harness.Run, mode: str, count: int = 1) -> int:
    """``readings``: one run's window (at ``--seconds``), then the numbers
    ``correct`` compares for the program and for the control, each against
    the cell's limits, and whether each comes out correct, as one JSON
    line; ``count`` is not used (one seed per process: the weights are the
    seed's).  ``sweep``: see :func:`sweep`."""
    import jax

    if mode == "sweep":
        return sweep(run)
    if mode != "readings":
        raise harness.BenchError(f"no tool mode {mode!r} in serve_open")
    cell, tr, cfg = harness.cell_spec(run)
    srv = Server(run.seed, cell, tr, cfg, run.notes["compiles"])
    warm(srv, int(cell["program"]["max_batch"]))
    lead, reqs = offered(tr, float(cell["rate_per_s"]), run.seconds)
    picks = sample(reqs, run.seed, int(cell["check"]["sample"]))
    t0 = time.perf_counter() + float(tr["lead_in_s"])
    srv.serve(lead + reqs, t0, deadline=t0 + run.seconds + float(tr["drain_s"]))
    captured = compared(srv, picks)
    docs, cold = srv.docs, sum(r.cold_first for r in reqs)
    del srv
    gc.collect()
    jax.clear_caches()
    got = check(run.seed, cfg, cell, docs, captured, control=True)
    line = {"seed": run.seed,
            "program": {c.name: c.value for c in got["checks"]},
            "program_correct": all(c.ok for c in got["checks"]),
            "control": {c.name: c.value for c in got["control"]},
            "control_correct": all(c.ok for c in got["control"]),
            "served_tokens": sum(len(r.tokens) for r, _ in captured),
            "reference_s": got["reference_s"],
            "cold_first_chunk_plans": cold,
            "compared": len(captured)}
    print(json.dumps(line), flush=True)
    return 0
