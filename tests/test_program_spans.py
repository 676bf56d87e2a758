"""The program's own profiler spans (``repro.*``): ``ServeEngine`` and
``AdmissionFrontEnd`` serving a stream, and ``Trainer.run``, under
``jax.profiler.trace``, read back from the ``.xplane.pb`` the profiler
writes. Each span is on the thread that drives the device, nests where
its layer does, and carries the args the per-layer metrics read."""

import glob
import os
import time
from collections import Counter

import jax
import numpy as np

from repro.configs import get_config
from repro.core.progress import ProgressEngine
from repro.models import api
from repro.serving.admission import AdmissionFrontEnd, make_offer
from repro.serving.engine import ServeEngine

CFG = get_config("qwen1.5-0.5b", smoke=True)

SERVE_SPANS = {
    "repro.serve.step",
    "repro.serve.prefill",
    "repro.serve.prefill.readback",
    "repro.serve.splice",
    "repro.serve.decode",
    "repro.serve.decode.dispatch",
    "repro.serve.decode.readback",
    "repro.serve.advance",
    "repro.admit.submit",
    "repro.admit.complete",
    "repro.admit.park",
}
TRAIN_CHILDREN = (
    "repro.train.prefetch",
    "repro.train.get_batch",
    "repro.train.h2d",
    "repro.train.dispatch",
    "repro.train.readback",
    "repro.train.bookkeeping",
)


def program_spans(trace_dir):
    """Every ``repro.*`` host event of the one trace under ``trace_dir``:
    (thread line, name, start ns, end ns, args)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    start = float(ev.start_ns)
                    out.append((line.name, ev.name, start, start + float(ev.duration_ns), dict(ev.stats)))
    return out


def named(spans, name):
    return [s for s in spans if s[1] == name]


def inside(child, parents):
    """The span of ``parents`` on ``child``'s thread that holds it, or None."""
    for p in parents:
        if p[0] == child[0] and p[2] <= child[2] and child[3] <= p[3]:
            return p
    return None


def test_serving_spans_nest_and_carry_args(tmp_path):
    params = api.init_params(CFG, jax.random.key(0))
    pe = ProgressEngine()
    eng = ServeEngine(CFG, params, max_batch=2, max_len=32, progress_engine=pe)
    front = AdmissionFrontEnd(eng)

    def offers():
        rng = np.random.default_rng(5)
        time.sleep(0.1)  # the front end parks on the mailbox meanwhile
        for i in range(5):
            yield make_offer(rng.integers(1, CFG.vocab, size=(4, 8)[i % 2]).astype(np.int32),
                             max_new_tokens=int(rng.integers(2, 5)))

    with jax.profiler.trace(str(tmp_path)):
        out = front.serve(offers())
    pe.stop_all()
    spans = program_spans(tmp_path)
    assert SERVE_SPANS <= {s[1] for s in spans}

    steps = named(spans, "repro.serve.step")
    assert sorted(s[4]["step_num"] for s in steps) == list(range(eng.steps))
    prefills, decodes = named(spans, "repro.serve.prefill"), named(spans, "repro.serve.decode")
    for child, parents in (
        ("repro.serve.prefill.readback", prefills),
        ("repro.serve.decode.dispatch", decodes),
        ("repro.serve.decode.readback", decodes),
        ("repro.serve.prefill", steps),
        ("repro.serve.decode", steps),
        ("repro.serve.splice", steps),
        ("repro.serve.advance", steps),
    ):
        assert all(inside(c, parents) for c in named(spans, child)), child
    assert len(named(spans, "repro.serve.decode.readback")) == len(decodes)
    assert all(1 <= d[4]["active"] <= eng.max_batch for d in decodes)

    # the rids on the admission and prefill spans are those that completed
    rids = sorted(c.rid for c in out)
    submits = named(spans, "repro.admit.submit")
    assert sorted(s[4]["rid"] for s in submits) == rids
    assert all(s[4]["mailbox_ms"] >= 0 for s in submits)
    assert sorted(p[4]["rid"] for p in prefills) == rids
    by_rid = {c.rid: c for c in out}
    for p in prefills:
        assert p[4]["S"] == by_rid[p[4]["rid"]].req.prompt.shape[0]
        assert p[4]["queued_ms"] >= 0
    splices = named(spans, "repro.serve.splice")
    assert {s[4]["rid"] for s in splices} <= set(rids)
    assert all(0 <= s[4]["slot"] < eng.max_batch for s in splices)


def test_trainer_spans_one_step_each(tmp_path):
    from repro.data.pipeline import DataConfig
    from repro.launch.train import Trainer
    from repro.optim.adamw import AdamWConfig

    tr = Trainer(
        CFG,
        AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=4),
        DataConfig(batch=2, seq=16, seed=0),
        ckpt_dir=str(tmp_path / "ck"),
        ckpt_every=2,
        autotune=False,
    )
    with jax.profiler.trace(str(tmp_path / "trace")):
        tr.run(4)
    tr.heartbeat.stop()
    tr.engine.stop_all()
    spans = program_spans(tmp_path / "trace")

    steps = named(spans, "repro.train.step")
    assert sorted(s[4]["step_num"] for s in steps) == [0, 1, 2, 3]
    counts = Counter(s[1] for s in spans)
    for child in TRAIN_CHILDREN:
        assert counts[child] == 4, child
        assert all(inside(c, steps) for c in named(spans, child)), child
    # a save fires inside step 2 alone (the final one, of step 3, is after
    # the loop); no failure was noted, so nothing is recovered
    (ckpt,) = named(spans, "repro.train.ckpt")
    assert inside(ckpt, steps)[4]["step_num"] == 2
    assert counts["repro.train.recover"] == 0
