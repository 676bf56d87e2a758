"""Serving cells: a mix's requests through ``AdmissionFrontEnd.serve`` over
a ``ServeEngine`` with a progress engine, the program's normal served path.

The engine instance is wrapped, not changed: ``submit`` ties each request
to the client's record, ``_prefill_request`` times the B=1 prefill and
stamps the first token, ``_decode_active`` times each batched decode and
stamps every token it emits, and ``step`` bounds the drain and opens and
closes the traced window between steps. Each wrapper opens a ``bench.*``
``TraceAnnotation`` so a trace can say what the host was doing.
"""

from __future__ import annotations

import gc
import queue
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import arch, mixes, reference
from bench.common import log, rng
from bench.harness import Profiler
from bench.runinfo import Decode, Prefill, Run, Track

CLOCK = time.perf_counter


class DrainTimeout(Exception):
    """The drain after the window passed its bound."""


class Instrumented:
    """Wraps one engine instance's methods and keeps what they saw."""

    def __init__(self, eng, profiler: Profiler):
        self.eng = eng
        self.profiler = profiler
        self.tracks: Dict[int, Track] = {}  # client index -> record
        self.by_prompt: Dict[int, int] = {}  # id(prompt array) -> client index
        self.by_rid: Dict[int, int] = {}
        self.prefills: List[Prefill] = []
        self.decodes: List[Decode] = []
        self.deadline = float("inf")
        self._wrap()

    def _wrap(self) -> None:
        eng = self.eng
        submit, prefill, decode, step = eng.submit, eng._prefill_request, eng._decode_active, eng.step

        def w_submit(prompt, max_new_tokens=16, eos_id=-1):
            idx = self.by_prompt.get(id(prompt))
            with jax.profiler.TraceAnnotation("bench.admit"):
                try:
                    req = submit(prompt, max_new_tokens, eos_id)
                except ValueError:
                    if idx is not None:
                        self.tracks[idx].rejected = True
                    raise
            if idx is not None:
                self.tracks[idx].rid = req.rid
                self.by_rid[req.rid] = idx
            return req

        def w_prefill(req):
            t0 = CLOCK()
            with jax.profiler.TraceAnnotation("bench.prefill"):
                out = prefill(req)
            t1 = CLOCK()
            self.prefills.append(Prefill(int(req.prompt.shape[0]), t0, t1))
            idx = self.by_rid.get(req.rid)
            if idx is not None:
                tr = self.tracks[idx]
                tr.prefill = (t0, t1)
                tr.tokens.append(t1)
            return out

        def w_decode():
            contexts = [int(eng.pos[i]) + 1 for i, r in enumerate(eng.slot_req) if r is not None]
            t0 = CLOCK()
            with jax.profiler.TraceAnnotation("bench.decode"):
                active, nxt = decode()
            t1 = CLOCK()
            if active:
                self.decodes.append(Decode(t0, t1, contexts))
            for i in active:
                idx = self.by_rid.get(eng.slot_req[i].rid)
                if idx is not None:
                    self.tracks[idx].tokens.append(t1)
            return active, nxt

        def w_step():
            now = CLOCK()
            if now > self.deadline:
                raise DrainTimeout()
            self.profiler.poll(now)
            with jax.profiler.TraceAnnotation("bench.step"):
                return step()

        eng.submit, eng._prefill_request, eng._decode_active, eng.step = w_submit, w_prefill, w_decode, w_step

    def forget(self) -> None:
        self.tracks.clear()
        self.by_prompt.clear()
        self.by_rid.clear()
        self.prefills.clear()
        self.decodes.clear()


def warm_up(eng, shapes, vocab: int, seed: int) -> None:
    """Compile every prefill shape the mix can send, the splice and the
    batched decode: one request per prompt length, two tokens each."""
    r = rng(seed, 9)
    for S in shapes:
        eng.submit(r.integers(0, vocab, S).astype(np.int32), max_new_tokens=2)
    eng.run_until_done()


def serve_window(eng, inst: Instrumented, mix: dict, vocab: int, seed: int, seconds: float,
                 profiler: Profiler, t_process_start: float):
    """Open the window, offer the mix, drain. Returns (Run fields, setup_s)."""
    from repro.serving.admission import AdmissionFrontEnd, make_offer

    front = AdmissionFrontEnd(eng, clock=CLOCK)
    closed = mix["loop"] == "closed"
    reqs = mixes.closed_pool(mix, vocab, seed) if closed else mixes.open_schedule(mix, vocab, seconds, seed)
    for r in reqs:
        inst.by_prompt[id(r.prompt)] = r.idx
    done_q: "queue.Queue[int]" = queue.Queue()
    t_open = CLOCK() + 0.05
    t_close = t_open + seconds
    lateness: List[float] = []

    def track(r: mixes.Req, due: float) -> Track:
        tr = Track(r.idx, int(r.prompt.shape[0]), r.max_new, due, prompt=r.prompt)
        inst.tracks[r.idx] = tr
        return tr

    if not closed:  # every request of an open loop is due in the window, sent or not
        for r in reqs:
            track(r, t_open + r.due)

    def record(r: mixes.Req, due: float) -> dict:
        now = CLOCK()
        tr = inst.tracks.get(r.idx) or track(r, due)
        tr.issued = now
        lateness.append(now - tr.due)
        return make_offer(r.prompt, max_new_tokens=r.max_new)

    def open_offers():
        for r in reqs:
            wait = t_open + r.due - CLOCK()
            if wait > 0:
                time.sleep(wait)
            yield record(r, t_open + r.due)

    def closed_offers():
        it = iter(reqs)
        clients = int(mix["arrivals"]["clients"])
        while CLOCK() < t_open:
            time.sleep(max(0.0, t_open - CLOCK()))
        for r in [next(it) for _ in range(clients)]:
            yield record(r, CLOCK())
        while True:
            left = t_close - CLOCK()
            if left <= 0:
                return
            try:
                done_q.get(timeout=left)
            except queue.Empty:
                return
            r = next(it, None)
            if r is None or CLOCK() >= t_close:
                return
            yield record(r, CLOCK())

    def on_complete(c) -> None:
        idx = inst.by_rid.get(c.rid)
        if idx is not None:
            tr = inst.tracks[idx]
            tr.done = c.t_done
            tr.out = list(c.req.out_tokens)
        done_q.put(c.rid)

    inst.deadline = t_close + float(mix.get("drain_s", 60))
    setup_s = t_open - t_process_start
    profiler.begin(t_open, t_close)
    drained = True
    try:
        front.serve(closed_offers() if closed else open_offers(), on_complete=on_complete)
    except DrainTimeout:
        drained = False
    t_end = CLOCK()
    profiler.finish()
    late = sorted(lateness)
    log(
        f"[generator] offers={len(late)} late_p50_ms={1e3 * late[len(late) // 2]:.3f} "
        f"late_p95_ms={1e3 * late[int(0.95 * (len(late) - 1))]:.3f} late_max_ms={1e3 * late[-1]:.3f} "
        f"over_10ms={sum(x > 0.01 for x in late)}"
        if late else "[generator] no offers"
    )
    log(f"[window] t_open->t_close {seconds:.3f} s, drain ended {t_end - t_close:.3f} s after close, "
        f"drained={drained}, rejected={len(front.rejected)}, engine_steps={front.steps}")
    return t_open, t_close, t_end, setup_s


def log_ttft_tail(tracks: List[Track], decodes: List[Decode], prefills: List[Prefill], k: int = 8) -> None:
    """Where the slowest first tokens spent their time: for the ``k`` longest,
    the generator's lateness, the wait before the prefill (and how much of it
    was decode steps and other prefills), and the prefill itself."""
    rows = []
    for t in tracks:
        if not t.tokens or t.prefill is None or t.issued is None:
            continue
        a, b = t.issued, t.prefill[0]
        dec = sum(max(0.0, min(d.t1, b) - max(d.t0, a)) for d in decodes)
        pre = sum(max(0.0, min(p.t1, b) - max(p.t0, a)) for p in prefills)
        rows.append(((t.tokens[0] - t.due) * 1e3, t, (a - t.due) * 1e3, (b - a) * 1e3, dec * 1e3, pre * 1e3,
                     (t.prefill[1] - t.prefill[0]) * 1e3))
    if not rows:
        return
    v = sorted(r[0] for r in rows)
    q = lambda p: v[min(len(v) - 1, int(p * (len(v) - 1)))]  # noqa: E731
    log(f"[ttft] n={len(v)} p50={q(0.5):.2f} p90={q(0.9):.2f} p95={q(0.95):.2f} p99={q(0.99):.2f} max={v[-1]:.2f} ms")
    for ttft, t, late, wait, dec, pre, own in sorted(rows, key=lambda r: -r[0])[:k]:
        log(f"[ttft] idx={t.idx} S={t.prompt_len} ttft={ttft:.2f} late={late:.2f} wait={wait:.2f} "
            f"(decode {dec:.2f}, prefills {pre:.2f}) prefill={own:.2f} ms")


def sample_checked(tracks: List[Track], k: int, seed: int) -> List[Track]:
    """``k`` finished requests drawn from the seed, the longest answer among them."""
    done = sorted((t for t in tracks if t.out), key=lambda t: t.idx)
    if not done:
        return []
    longest = max(done, key=lambda t: (len(t.out), t.prompt_len, -t.idx))
    rest = [t for t in done if t is not longest]
    pick = rng(seed, 5).permutation(len(rest))[: max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def logit_gaps(s, w, sample: List[Track], pad_to: int, control: bool):
    """The gap of every served token under the float32 reference, and of the
    float8 control's first choice at the same positions (None without
    ``control``): two flat arrays, one entry per served token compared."""
    gaps, ctl = [], []
    for t in sample:
        seq = np.concatenate([np.asarray(t.prompt, np.int32), np.asarray(t.out[:-1], np.int32)])
        toks = np.zeros(pad_to, np.int32)
        toks[: seq.shape[0]] = seq
        chosen = np.zeros(pad_to, np.int32)
        pos = np.arange(t.prompt_len - 1, t.prompt_len - 1 + len(t.out))
        chosen[pos] = np.asarray(t.out, np.int32)
        gaps.append(np.asarray(reference.served_gaps(s, w, toks, chosen))[pos])
        if control:
            ctl.append(np.asarray(reference.control_gaps(s, w, toks))[pos])
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    return flat, (np.concatenate(ctl) if ctl else np.zeros(0)) if control else None


def gap_readings(g: np.ndarray) -> dict:
    """``logit_gap``: the widest gap; ``logit_gap_mean``: the mean over every
    token compared. None where nothing was compared."""
    if g.size == 0:
        return {"logit_gap": None, "logit_gap_mean": None}
    return {"logit_gap": float(g.max()), "logit_gap_mean": float(g.mean())}


def setup(cfg_file: dict, mix: dict, seed: int, seed32: int, shapes, trace: bool, faults=None):
    """Weights, engine, instrumentation and the warm-up of the prompt
    lengths ``shapes``: everything before the window."""
    from repro.core.progress import ProgressEngine
    from repro.models import api
    from repro.serving.engine import ServeEngine

    from bench import harness, weights

    cfg = harness.model_config(cfg_file["model"], mix.get("model_options"))
    s = arch.spec(cfg_file)
    w = weights.make(s, seed32)
    params = arch.of(s).to_program(s, w)
    weights.check_layout(params, jax.eval_shape(lambda k: api.init_params(cfg, k), jax.random.key(0)))
    e = mix["engine"]
    eng = ServeEngine(cfg, params, max_batch=int(e["max_batch"]), max_len=int(e["max_len"]),
                      progress_engine=ProgressEngine())
    profiler = Profiler(trace, CLOCK)
    inst = Instrumented(eng, profiler)
    if faults is not None:
        faults(eng)
    warm_up(eng, shapes, s.vocab, seed)
    inst.forget()
    return cfg, s, w, eng, inst, profiler


def run(cell: str, cfg_file: dict, mix: dict, seed: int, seconds: float, trace: bool,
        control: bool, seed32: int, peaks: Optional[dict], n_chips: int, t_process_start: float,
        faults=None):
    """One run of a serving cell. Returns (Run, readings, attempted, failed,
    setup_s). With ``control`` the float8 control's gaps stand in the
    program's place among the readings."""
    from bench import harness

    shapes = mixes.prompt_shapes(mix, seconds)
    cfg, s, w, eng, inst, profiler = setup(cfg_file, mix, seed, seed32, shapes, trace, faults)
    e = mix["engine"]
    watch = harness.CompileWatch()
    t_open, t_close, t_end, setup_s = serve_window(eng, inst, mix, s.vocab, seed, seconds, profiler, t_process_start)
    log(f"[compile] backend compiles inside the window and drain: {watch.compiles}")

    tracks = sorted(inst.tracks.values(), key=lambda t: t.idx)
    if mix["loop"] == "open":
        log_ttft_tail(tracks, inst.decodes, inst.prefills)
    out = Run(
        kind="serve", cell=cell, spec=s, peaks=peaks, t_open=t_open, t_close=t_close, t_end=t_end,
        max_batch=int(e["max_batch"]),
        flash_prefill=cfg.attn_impl == "flash" and hasattr(s, "flash_attention_call"),
        requests=tracks, prefills=list(inst.prefills), decodes=list(inst.decodes),
        trace=profiler.events, trace_span=profiler.span, n_chips=n_chips,
    )
    out.memory_peak_bytes = harness.memory_peak_bytes(n_chips)

    failed = [t for t in tracks if t.rejected or t.done is None]
    short = [t for t in tracks if t.out is not None and len(t.out) != t.max_new]

    # the program's state goes before the reference runs
    del eng, inst
    gc.collect()
    t0 = time.perf_counter()
    sample = sample_checked(tracks, int(mix.get("check", {}).get("requests", 6)), seed)
    gaps, gaps_ctl = logit_gaps(s, w, sample, int(e["max_len"]), control)
    log(f"[reference] {len(sample)} requests, {gaps.size} served tokens compared in {time.perf_counter() - t0:.3f} s;"
        f" program {gap_readings(gaps)}")
    readings = {"unanswered": len(failed), "wrong_length": len(short), **gap_readings(gaps)}
    if control:
        readings.update(gap_readings(gaps_ctl))
        log(f"[control] the float8 control in the program's place: {gap_readings(gaps_ctl)}")
    return out, readings, len(tracks), len(failed), setup_s
