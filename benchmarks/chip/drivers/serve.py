"""Serve cells: open-loop requests through the repo's ``ServeEngine``.

The engine decodes a fixed batch with continuous batching; a prompt is fed
one token per step.  Requests are due on the mix's schedule (seconds from the
window's start) and are handed to the engine at the first step boundary
after they are due; each is timed from its due time.  Every request due in
the window is followed to its end.

``ServeEngine`` hands results out only when ``run()`` drains, so the window
reads each token's time by the narrowest observation there is: after each
decode step it looks at the token lists of the requests in the batch.  Once
the window has closed and the engine is freed, the reference runs over a
sample of the finished requests, drawn from the seed with the longest among
them, and the widest gap by which a served token's logit lies below the
reference's best is compared with its limit.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import jax
import numpy as np

from chipbench import traffic as tg
from chipbench.outcome import Outcome, WindowTracer, compiles
from chipbench.seeds import sub_seed
from repro.models import ModelConfig, build_model
from repro.serve import ServeEngine


class ObservedEngine(ServeEngine):
    """The engine, handed its requests on schedule and watched per step."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.begin([], time.perf_counter(), WindowTracer(None, 0.0), False)

    def begin(self, requests: List[tg.Request], t_open: float, tracer,
              count_work) -> None:
        self.schedule = requests
        self.next = 0
        self.t_open = t_open
        self.tracer = tracer
        self.count_work = count_work
        self.rid_of: Dict[int, int] = {}          # engine id -> schedule index
        self.stamps: Dict[int, List[float]] = {}
        self.requests: Dict[int, object] = {}
        self.since: Dict[int, int] = {}           # steps each request has run
        self.traced_positions: List[List[int]] = []

    def next_due(self) -> float:
        if self.next >= len(self.schedule):
            return math.inf
        return self.t_open + self.schedule[self.next].due_s

    def _fill_slots(self) -> None:
        now = time.perf_counter()
        self.tracer.poll(now)
        with jax.profiler.TraceAnnotation("bench.admit"):
            while self.next < len(self.schedule) and \
                    self.t_open + self.schedule[self.next].due_s <= now:
                r = self.schedule[self.next]
                rid = self.submit(r.prompt, max_new_tokens=r.max_new)
                self.rid_of[rid] = self.next
                self.stamps[rid] = []
                self.next += 1
            super()._fill_slots()

    def _decode_one_step(self, done) -> None:
        active = [s.request for s in self._slots if s.request is not None]
        for r in active:
            self.requests[r.request_id] = r
        tracing = self.tracer.started and not self.tracer.done
        if tracing and self.count_work:
            self.traced_positions.append(
                [self.since.get(r.request_id, 0) for r in active])
        with jax.profiler.TraceAnnotation("bench.decode_step"):
            super()._decode_one_step(done)
        now = time.perf_counter()
        for r in active:
            rid = r.request_id
            self.since[rid] = self.since.get(rid, 0) + 1
            st = self.stamps.get(rid)
            if st is not None:
                st.extend([now] * (len(r.tokens) - len(st)))


def serve_window(eng: ObservedEngine, requests, seconds: float, tracer,
                 count_work: bool = False):
    """Run one window of ``requests`` (due in [0, seconds)) to the end of the
    last; returns (t_open, t_done)."""
    t_open = time.perf_counter()
    eng.begin(requests, t_open, tracer, count_work)
    tracer.open(t_open)
    while True:
        eng.run(max_steps=1 << 62)
        if eng.next >= len(requests) and eng._queue.empty() and \
                not any(s.request for s in eng._slots):
            break
        with jax.profiler.TraceAnnotation("bench.await_arrival"):
            wait = eng.next_due() - time.perf_counter()
            tracer.poll(time.perf_counter())
            if wait > 0:
                time.sleep(min(wait, 0.01))
    t_done = time.perf_counter()
    tracer.close()
    return t_open, t_done


def latencies(eng: ObservedEngine, requests) -> Dict[str, object]:
    ttft, gaps, unfinished = [], [], 0
    for rid, idx in eng.rid_of.items():
        st = eng.stamps[rid]
        r = eng.requests.get(rid)
        if r is None or len(st) != requests[idx].max_new \
                or len(r.tokens) != requests[idx].max_new:
            unfinished += 1
            continue
        ttft.append(st[0] - (eng.t_open + requests[idx].due_s))
        gaps.extend(np.diff(st).tolist())
    unfinished += len(requests) - len(eng.rid_of)
    return {"ttft": ttft, "gaps": gaps, "unfinished": unfinished}


def sample(eng: ObservedEngine, requests, seed: int, k: int):
    """(prompt, served tokens) of ``k`` finished requests drawn from the
    seed, the longest among them."""
    done = [rid for rid, idx in eng.rid_of.items()
            if rid in eng.requests
            and len(eng.requests[rid].tokens) == requests[idx].max_new]
    if not done:
        return []
    size = lambda rid: len(eng.requests[rid].prompt) + len(eng.requests[rid].tokens)
    longest = max(done, key=size)
    rest = [rid for rid in done if rid != longest]
    rng = np.random.default_rng(sub_seed(seed, "check"))
    pick = [longest] + list(rng.choice(rest, min(k - 1, len(rest)),
                                       replace=False))
    return [(list(eng.requests[r].prompt), list(eng.requests[r].tokens))
            for r in pick]


class ServeCell:
    """The model and its compiled steps, built once per process."""

    def __init__(self, cell, reference):
        self.cell, self.ref = cell, reference
        self.cfg, self.tr = cell.config, cell.traffic
        self.model = build_model(ModelConfig(**reference.program_kwargs(self.cfg)))

    def engine(self, seed: int) -> ObservedEngine:
        params = self.ref.init_params(self.cfg, sub_seed(seed, "weights"))
        eng = ObservedEngine(self.model, params,
                             batch_size=self.tr["batch"],
                             max_context=self.tr["max_context"],
                             eos_token=-1)
        # compile and warm the engine's two programs and its host read
        eng.submit([1], max_new_tokens=2)
        eng.run(max_steps=8)
        jax.block_until_ready(eng.cache)
        return eng

    def requests(self, seed: int, seconds: float, rate=None):
        return tg.open_loop(self.tr, self.cfg["vocab_size"], seed, seconds,
                            rate)

    def served_gap(self, seed: int, served, control: bool = False):
        pad = self.tr["prompt"]["max"] + self.tr["output"]["max"]
        return self.ref.served_gaps(self.cfg, sub_seed(seed, "weights"),
                                    served, pad, control)


def run(cell, ctx) -> Outcome:
    sc = ServeCell(cell, ctx.reference)
    seed = ctx.seed
    requests = sc.requests(seed, ctx.seconds)
    eng = sc.engine(seed)
    tracer = ctx.tracer(cell.traffic.get("trace", {}))
    tracer.before_window()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    steps0 = eng.steps_run
    compiled0 = compiles()
    t_open, t_done = serve_window(eng, requests, ctx.seconds, tracer,
                                  count_work=True)
    window_compiles = compiles() - compiled0
    memory = ctx.memory_peak()
    lat = latencies(eng, requests)
    served = sample(eng, requests, seed, cell.traffic["check"]["sample"])
    positions = eng.traced_positions
    steps = eng.steps_run - steps0
    del eng
    gc.collect()

    gaps, _ = sc.served_gap(seed, served) if served else (np.array([]), None)
    n_served = int(sum(len(t) for _, t in served))
    numbers = {
        "unfinished": float(lat["unfinished"]),
        "served_gap": float(np.max(gaps)) if gaps.size else math.inf,
    }
    return Outcome(
        attempted=len(requests), failed=lat["unfinished"],
        end_to_end={
            "setup_s": setup_s,
            "ttft_p90_s": tg.percentile(lat["ttft"], 90),
            "itl_p95_ms": 1e3 * tg.percentile(lat["gaps"], 95),
        },
        numbers=numbers,
        counters={
            "traced_steps": len(positions),
            "traced_flops": sum(ctx.reference.decode_flops(cell.config, p)
                                for p in positions),
            "traced_bytes": sum(ctx.reference.decode_bytes(cell.config, p)
                                for p in positions),
            "decode_steps": steps,
            "compiles_in_window": window_compiles,
            "drain_s": t_done - t_open - ctx.seconds,
            "served_checked": n_served,
            "chips": cell.chips,
        },
        memory_peak_bytes=memory)
