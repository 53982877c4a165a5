"""The serving runner: `ServingEngine.add_request` / `ServingEngine.step`
under an open loop, over whichever model the configuration's `program`
builds. From the engine it takes what every engine has (`cfg.max_seq`,
`cfg.token_budget`, `cfg.vocab_size`, `add_request`, `step`, `pending`,
`run_to_completion`, a request's `cached` and `sched_t0`), from the
configuration no size of a model.

One loop in one thread, as `inference/router.py` drives a replica: admit
what is due, `engine.step()` while anything is pending, else sleep to the
next arrival. Times are the runner's own clock: a request's first token is
timed from when it was DUE, not from when it was admitted. Arrivals begin
`ramp_s` before the window opens (the ramp is set-up) and go on until the
last request due in the window has finished, so that it finishes under
load. Once the window has closed, the memory peak is read, the engine is
freed, and a sample of the finished requests (drawn from the seed, the
longest among them) goes through the plain reference once.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from .. import compare, traffic_gen, weights
from ..harness import (BenchmarkError, Compared, CompileCounter, RunResult,
                       Tracer, annotate, load_module, memory_peak_bytes,
                       percentile)

DRAIN_LIMIT_S = 60.0         # past the window's close, for late answers


def build_engine(config: dict, seed: int):
    """(model, engine, weight shapes) from the program the configuration
    names: `benchmark/programs/<program>.py`."""
    program = load_module("programs", config["program"])
    return program.build_engine(config, seed)


class _Record:
    __slots__ = ("due", "prompt", "max_new", "req", "first_t", "last_t",
                 "tokens", "failed", "measured")

    def __init__(self, due, prompt, max_new, measured):
        self.due, self.prompt, self.max_new = due, prompt, max_new
        self.measured = measured
        self.req = None
        self.first_t = self.last_t = None
        self.tokens = []
        self.failed = False

    @property
    def done(self):
        return self.failed or len(self.tokens) >= self.max_new


def _processed(records, at_start):
    """What the steps since `at_start` (rid -> cached then) processed:
    tokens, and the positions each attended (itself included)."""
    tokens = context = 0
    for rid, rec in records.items():
        if rec.req is None:
            continue
        c0 = at_start.get(rid, 0)
        c1 = rec.req.cached
        if c1 > c0:
            tokens += c1 - c0
            context += (c1 * (c1 + 1) - c0 * (c0 + 1)) // 2
    return tokens, context


def run(cell, seed: int, seconds: float, trace: bool, devices, t_start):
    config, mix = cell.config, cell.traffic
    if mix["sampling"] != "greedy":
        raise BenchmarkError("the served-logit check needs greedy requests")
    counter = CompileCounter()
    phases = {"start": time.perf_counter() - t_start}   # imports, the chip
    model, engine, shapes = build_engine(config, seed)
    phases["engine"] = time.perf_counter() - t_start
    max_seq, vocab = engine.cfg.max_seq, engine.cfg.vocab_size
    schedule = traffic_gen.open_loop(
        mix, seed, vocab, [mix["ramp_s"], seconds, mix["tail_s"]])
    too_long = [r for r in schedule
                if len(r.prompt) + r.max_new > max_seq]
    if too_long:
        raise BenchmarkError(f"{len(too_long)} request(s) pass max_seq")

    # warm-up: what the loop calls, nothing else. A fresh prefill, then
    # mixed steps (chunked prefill beside decode), then the sampler.
    rng = np.random.default_rng([seed, 9])
    for n in (engine.cfg.token_budget + 8, 24):
        engine.add_request(rng.integers(1, vocab, n).tolist(),
                           max_new_tokens=3)
        engine.step()
    engine.run_to_completion()
    phases["warm_up"] = time.perf_counter() - t_start

    tracer = Tracer(trace, cell.name)
    records = {}                       # rid -> _Record
    emitted = []                       # time of every token emitted
    step_spans = []
    nxt = 0
    t_begin = time.perf_counter()
    t_open = t_begin + mix["ramp_s"]
    t_nominal = t_open + seconds
    opened = False
    traced_from, traced_work, traced_sampled = None, None, 0
    failed = attempted = 0
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            opened = True
            counter.open = True
            tracer.start()
            traced_from = {rid: rec.req.cached for rid, rec in
                           records.items() if rec.req is not None}
            setup_s = time.perf_counter() - t_start
        measured_left = any(r.measured and not r.done
                            for r in records.values())
        if now >= t_nominal and not measured_left \
                and (nxt >= len(schedule)
                     or t_begin + schedule[nxt].due_s >= t_nominal):
            break
        if now > t_nominal + DRAIN_LIMIT_S:
            break
        if nxt < len(schedule) and t_begin + schedule[nxt].due_s <= now:
            with annotate("admit"):
                while nxt < len(schedule) \
                        and t_begin + schedule[nxt].due_s <= now:
                    item = schedule[nxt]
                    nxt += 1
                    due = t_begin + item.due_s
                    rec = _Record(due, item.prompt, item.max_new,
                                  item.phase == 1)
                    attempted += rec.measured
                    try:
                        rid = engine.add_request(
                            item.prompt, max_new_tokens=item.max_new)
                    except (RuntimeError, ValueError):
                        rec.failed = True
                        failed += rec.measured
                        records[-nxt] = rec
                        continue
                    rec.req = next(r for r in engine.pending()
                                   if r.rid == rid)
                    records[rid] = rec
        if engine.pending():
            t0 = time.perf_counter()
            with annotate("engine_step"):
                produced = engine.step()
            t1 = time.perf_counter()
            step_spans.append((t0, t1))
            for rid, tok in produced:
                rec = records[rid]
                if rec.first_t is None:
                    rec.first_t = t1
                rec.last_t = t1
                rec.tokens.append(int(tok))
                emitted.append(t1)
            if tracer.running:
                traced_sampled += len(produced)
                if tracer.due():
                    tracer.stop()
                    traced_work = _processed(records, traced_from)
        elif nxt < len(schedule):
            with annotate("wait_arrival"):
                time.sleep(max(0.0, min(
                    t_begin + schedule[nxt].due_s - time.perf_counter(),
                    0.05)))
        else:
            break
    t_end = time.perf_counter()
    if tracer.running:
        tracer.stop()
        traced_work = _processed(records, traced_from)
    compiles = counter.close()

    measured = [r for r in records.values() if r.measured]
    finished = [r for r in measured if not r.failed
                and len(r.tokens) >= r.max_new]
    failed += sum(1 for r in measured if not r.failed
                  and len(r.tokens) < r.max_new)
    if not finished:
        raise BenchmarkError("no request due in the window finished")
    t_last = max(r.last_t for r in finished)
    ttft = [(r.first_t - r.due) * 1e3 for r in finished]
    end_to_end = {
        # the window's own seconds: what the drain after it holds depends
        # on which request came last, which is the seed's doing
        "serve_tokens_s": sum(1 for t in emitted
                              if t_open <= t < t_nominal) / seconds,
        "ttft_p90_ms": percentile(ttft, 90),
        "ttft_p95_ms": percentile(ttft, 95),
        "tpot_p95_ms": percentile(
            [(r.last_t - r.first_t) / (len(r.tokens) - 1) * 1e3
             for r in finished if len(r.tokens) > 1], 95),
        "setup_s": setup_s}
    stats = {
        "ttft_ms": ttft,
        "queue_wait_ms": [(r.req.sched_t0 - r.due) * 1e3 for r in finished
                          if r.req.sched_t0 is not None],
        "requests": len(measured), "steps": len(step_spans),
        "setup_phases_s": phases,
        "drain_s": t_last - t_nominal, "late_end_s": t_end - t_nominal,
        "step_ms_median": percentile(
            [(b - a) * 1e3 for a, b in step_spans], 50)}
    if traced_work:
        stats["traced_work"] = {"tokens": traced_work[0],
                                "context": traced_work[1],
                                "sampled": traced_sampled}

    # the sample for the reference: drawn from the seed, the longest in it
    order = np.random.default_rng([seed, 3]).permutation(len(finished))
    longest = max(range(len(finished)), key=lambda i: len(
        finished[i].prompt) + len(finished[i].tokens))
    picks = [longest] + [int(i) for i in order if i != longest]
    sample = [(finished[i].prompt, finished[i].tokens)
              for i in picks[:mix["check_requests"]]]

    peak = memory_peak_bytes(devices)
    for rec in records.values():
        rec.req = None
    del engine, model, records, finished, measured
    gc.collect()

    ref = load_module("reference", config["reference"])
    w = weights.make_like(shapes, config, seed, donate=False)
    gap = 0.0
    for prompt, tokens in sample:
        logits = ref.logits_at(w, prompt + tokens, len(prompt) - 1, config,
                               pad_to=max_seq)
        gap = max(gap, compare.served_gap(
            np.asarray(logits)[:len(tokens)], tokens))
    stats["checked_tokens"] = sum(len(t) for _, t in sample)
    stats["sample"], stats["weight_shapes"] = sample, shapes
    stats["max_seq"] = max_seq
    return RunResult(
        attempted=attempted, failed=failed, end_to_end=end_to_end,
        compared={"served_logit_gap": Compared(
            gap, cell.limits["served_logit_gap"])},
        stats=stats, trace=tracer.load() if trace else None,
        memory_peak_bytes=peak, compiles_in_window=compiles)
