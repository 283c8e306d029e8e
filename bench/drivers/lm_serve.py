"""Driver of spiking-LM serving cells: the program's front door
(``repro.server.FrontDoor``) over its paged continuous-batching scheduler
(``repro.serving.BatchScheduler``), loaded in this process by a closed
loop of clients or by an open loop on a schedule, as the mix says.

Set-up makes the weights on the device from the seed, builds the server
the configuration describes, and sends a short warm-up load through it
that opens, shares, copies, zeroes and releases pages, so that every
program the window runs is compiled (or read from the cache) before the
window opens; a mix's system prefixes are then cached, as a deployment's
are.  The window runs the mix for ``--seconds``; every token is stamped
when its client receives it.  Afterwards a sample of the finished
requests, drawn from the seed with the longest among them, is compared
with the plain reference (``bench/reference/<reference>.py``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import itertools
import time
from typing import Dict, List, Optional

import numpy as np

import harness
import stats
import trace_reduce

_now = time.perf_counter


@dataclasses.dataclass
class Rec:
    """One request as its client saw it."""

    i: int
    prompt: List[int]
    max_new: int
    seed: int
    due: float  # when it was due to be sent (perf_counter s)
    fid: Optional[int] = None  # the front door's request id
    t_submit: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return len(self.tokens) == self.max_new


# -- weights -------------------------------------------------------------


def init_weights(c: Dict, key):
    """Float32 weights in the served layout: normal with std 1/sqrt(first
    axis of each layer's matrix), as the repository's own initialiser draws
    them; norm scales are 1."""
    import jax
    import jax.numpy as jnp

    d, h, kv, hd = c["d_model"], c["num_heads"], c["num_kv_heads"], c["head_dim"]
    dff, v, nl = c["d_ff"], c["vocab_size"], c["num_layers"]
    shapes = {"table": ((v, d), v), "unembed": ((d, v), d),
              "wq": ((nl, d, h, hd), d), "wk": ((nl, d, kv, hd), d),
              "wv": ((nl, d, kv, hd), d), "wo": ((nl, h, hd, d), h),
              "wi": ((nl, d, dff), d), "wo2": ((nl, dff, d), dff)}
    ks = jax.random.split(key, len(shapes))
    a = {name: jax.random.normal(k, shp, jnp.float32) / np.sqrt(fan)
         for k, (name, (shp, fan)) in zip(ks, shapes.items())}
    ones = jnp.ones((nl, d), jnp.float32)
    return {
        "embed": {"table": a["table"]},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "periods": {"blk0": {
            "mixer": {"wq": a["wq"], "wk": a["wk"], "wv": a["wv"], "wo": a["wo"]},
            "mlp": {"wi": a["wi"], "wo": a["wo2"]},
            "norm1": {"scale": ones}, "norm2": {"scale": ones}}},
        "unembed": {"w": a["unembed"]},
    }


def make_weights(c: Dict, seed: int):
    """The weights of ``init_weights`` from the seed, in one jitted call."""
    import jax

    return jax.jit(lambda k: init_weights(c, k))(harness.seed_key(seed, 11))


# -- the server ------------------------------------------------------------


_SIZES = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
          "d_ff", "vocab_size", "spike_T", "dtype")


def program_config(c: Dict):
    """The program's model configuration with the sizes of the file."""
    from repro.configs.registry import get_config

    cfg = dataclasses.replace(get_config(c["registry_name"]),
                              **{k: c[k] for k in _SIZES}).validate()
    if not (cfg.spiking and cfg.attention_kind == "ssa"):
        raise harness.CellError(f"{c['name']}: not a spiking SSA decoder")
    return cfg


def build_server(c: Dict, params):
    """The program's scheduler as the configuration describes it."""
    from repro.engine import get_backend
    from repro.serving import BatchScheduler

    sv = c["serving"]
    return BatchScheduler(
        params, program_config(c), get_backend(sv["backend"]),
        slots=sv["slots"], cache_len=c["cache_len"], paged=sv["paged"],
        page_len=sv["page_len"], decode_kernel=sv["decode_kernel"])


class StepLog:
    """Host spans around the scheduler's calls, and what each batched step
    computed: the live positions of every occupied slot."""

    def __init__(self, sch):
        self.steps: List[tuple] = []  # (t_done, [live per occupied slot])
        step, admit, decode = sch.step, sch.admit, sch._decode

        def step_():
            with harness.span("bench.sched_step"):
                return step()

        def admit_():
            with harness.span("bench.sched_admit"):
                return admit()

        def decode_(*args):
            lives = [sch._slot_pos[s] + 1 for s in range(sch.slots)
                     if sch._slot_req[s] is not None]
            with harness.span("bench.decode_dispatch"):
                out = decode(*args)
            self.steps.append((_now(), lives))
            return out

        sch.step, sch.admit, sch._decode = step_, admit_, decode_


def _front(sch, admits: List):
    from repro.server import FrontDoor

    front = FrontDoor(sch, max_queue=4096)
    pump = front._pump_once

    def pump_():
        with harness.span("bench.pump"):
            return pump()

    front._pump_once = pump_

    def sink(ev):
        if ev.get("event") == "admit":
            admits.append(ev)

    front.obs.tracer.add_sink(sink)
    return front


async def _client_request(front, rec: Rec, recs: List[Rec]):
    recs.append(rec)
    with harness.span("bench.submit"):
        ts = await front.submit(rec.prompt, rec.max_new, seed=rec.seed)
    rec.t_submit = _now()
    rec.fid = ts.request_id
    async for tok in ts:
        rec.times.append(_now())
        rec.tokens.append(tok)


async def _drive(front, traffic, seconds: float, tw=None, trace_at=(0, 0)):
    """The mix through the front door for ``seconds``: a closed loop of
    ``clients``, each sending its next request once the last one finished,
    or an open loop sending on the mix's schedule whether or not earlier
    requests finished."""
    recs: List[Rec] = []
    await front.start()
    t0 = _now()
    end = t0 + seconds
    arr = traffic.mix["arrivals"]
    nxt = itertools.count()

    async def client():
        while _now() < end:
            i = next(nxt)
            prompt, max_new, seed = traffic.request(i)
            await _client_request(front, Rec(i, prompt, max_new, seed, _now()),
                                  recs)

    tasks = []

    async def sender():
        due = traffic.due_times(int(arr["rate_rps"] * seconds * 2) + 64)
        for i, d in enumerate(due):
            if d >= seconds:
                return
            wait = t0 + d - _now()
            if wait > 0:
                await asyncio.sleep(wait)
            prompt, max_new, seed = traffic.request(i)
            tasks.append(asyncio.ensure_future(_client_request(
                front, Rec(i, prompt, max_new, seed, t0 + d), recs)))
        raise AssertionError("the schedule ran out before the window")

    if traffic.closed:
        tasks.extend(asyncio.ensure_future(client())
                     for _ in range(arr["clients"]))
        send_task = None
    else:
        send_task = asyncio.ensure_future(sender())
    if tw is not None:  # off the event loop, which keeps sending on time
        loop = asyncio.get_running_loop()
        await asyncio.sleep(max(t0 + trace_at[0] - _now(), 0))
        await loop.run_in_executor(None, tw.start)
        await asyncio.sleep(max(t0 + trace_at[1] - _now(), 0))
        await loop.run_in_executor(None, tw.stop)
    await asyncio.sleep(max(end - _now(), 0))
    failed = front.failed  # before stop() fails what is still in flight
    await front.stop()
    if send_task is not None:
        await send_task
    await asyncio.gather(*tasks, return_exceptions=True)
    return recs, t0, end, failed


def _warm_up(sch, traffic) -> None:
    """A short load straight into the scheduler: prompts that share a
    prefix (hits and copy-on-write), partial and whole pages, evictions."""
    prompt, _, _ = traffic.request(0)
    base = (list(prompt) * 20)[:20]
    for p, n, s in [(base, 12, 1), (base, 12, 2), (base[:13], 9, 3),
                    (base[::-1], 17, 4)]:
        sch.submit(np.asarray(p, np.int32), n, seed=s)
    sch.run()
    sch.reset()
    # a deployment's system prompts are cached: fill their pages
    for i, prefix in enumerate(traffic.prefixes):
        sch.submit(np.asarray(prefix + [0], np.int32), 1, seed=i)
    sch.run()


# -- metrics -----------------------------------------------------------------


def _end_to_end(recs: List[Rec], t0: float, end: float, seconds: float) -> Dict:
    toks = sum(1 for r in recs for t in r.times if t <= end)
    out = {"tok_s": toks / seconds, "tokens_in_window": toks}
    due = [r for r in recs if r.due < end]
    if due:
        ttft = [((r.times[0] if r.times and r.times[0] <= end else end) - r.due)
                for r in due]
        gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])
                if b <= end]
        out.update(ttft_p95_ms=1e3 * stats.percentile(ttft, 95),
                   ttft_p50_ms=1e3 * stats.percentile(ttft, 50),
                   n_due=len(due), n_first_token=sum(1 for r in due if r.times
                                                     and r.times[0] <= end))
        if gaps:
            out.update(itl_p95_ms=1e3 * stats.percentile(gaps, 95),
                       itl_p50_ms=1e3 * stats.percentile(gaps, 50),
                       n_gaps=len(gaps))
        late = [r.t_submit - r.due for r in due if r.t_submit]
        out.update(lateness_p95_ms=1e3 * stats.percentile(late, 95),
                   lateness_max_ms=1e3 * max(late))
    return out


def _sample(recs: List[Rec], seed: int, n: int) -> List[Rec]:
    """``n`` finished requests drawn from the seed, the longest among them."""
    done = [r for r in recs if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + r.max_new)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 5])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[k] for k in sorted(pick)]


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        devs, hooks: Dict) -> Dict:
    c = dict(cell.config)
    counter = harness.CompileCounter()
    params = make_weights(c, seed)
    sch = build_server(c, params)
    if "server" in hooks:
        hooks["server"](sch)
    from repro.obs import Telemetry

    sch.attach_obs(Telemetry.create())
    traffic = cell.traffic().make(cell.mix, seed, c["vocab_size"])
    _warm_up(sch, traffic)
    log = StepLog(sch)
    admits: List = []
    tw = harness.TraceWindow(cell.name) if trace else None
    lead = min(2.0, seconds / 4)
    trace_at = (lead, min(seconds, lead + harness.TRACE_SECONDS))
    stats0 = dataclasses.replace(sch.stats)
    n_compiles0 = counter.n
    setup_s = _now() - t_start
    front = _front(sch, admits)
    recs, t0, end, failed = asyncio.run(_drive(front, traffic, seconds, tw,
                                               trace_at))
    compiles_in_window = counter.n - n_compiles0
    if "records" in hooks:
        hooks["records"].update(recs=recs, t0=t0, end=end, admits=admits)
    st = sch.stats
    device = harness.device_info(devs)
    e2e = _end_to_end(recs, t0, end, seconds)
    harness.info(setup_s=setup_s, compiles_in_window=compiles_in_window,
                 requests_sent=len(recs),
                 requests_finished=sum(r.finished for r in recs),
                 requests_failed=failed, decode_steps=st.decode_steps,
                 prefill_tokens=st.prefill_tokens,
                 decoded_tokens=st.decoded_tokens,
                 prefix_hit_tokens=st.prefix_hit_tokens, **e2e)
    ctx = {"config": c, "peaks": None,
           "stats": st, "stats0": stats0, "recs": recs, "t0": t0, "end": end,
           "steps": log.steps, "admits": admits, "e2e": e2e,
           "trace": None, "trace_window": None}
    breakdown = None
    if trace:
        ctx["peaks"] = harness.peaks_for(device["kind"])
        red = trace_reduce.reduce(tw.xplane(), tw.window_s)
        ctx["trace"] = red
        ctx["trace_window"] = (tw.t0, tw.t1)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = red["breakdown"]
    # free the server before the reference runs: the process's peak is read
    sample = _sample(recs, seed, c["check"]["sample_requests"])
    samples = [{"prompt": r.prompt, "tokens": list(r.tokens), "seed": r.seed}
               for r in sample]
    if "served" in hooks:
        samples = hooks["served"](samples)
    del sch, front, log
    gc.collect()
    ref = cell.reference()
    cmp = (ref.compare(params, c, samples) if samples
           else {"max_logit_gap": float("inf"), "tokens_compared": 0})
    if "control" in hooks:
        hooks["control"](ref, params, c, samples, cmp)
    limit = c["check"]["max_logit_gap"]
    checks = {"max_logit_gap": {"value": cmp["max_logit_gap"], "limit": limit},
              "tokens_compared": {"value": cmp["tokens_compared"],
                                  "limit": c["check"]["min_tokens_compared"]}}
    correct = (cmp["max_logit_gap"] <= limit
               and cmp["tokens_compared"] >= c["check"]["min_tokens_compared"]
               and failed == 0)
    if trace:
        metrics = harness.read_per_layer(cell.per_layer(), ctx)
    else:
        metrics = {}
        for m in cell.end_to_end():
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return {"correct": correct, "attempted": sum(1 for r in recs if r.due < end),
            "failed": failed, "metrics": metrics, "device": device,
            "checks": checks, "breakdown": breakdown}
