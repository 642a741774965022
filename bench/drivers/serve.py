"""Driver of the ``serve`` cells: the program's ``Engine`` under a closed
loop of callers, every token timestamped when its id is on the host.

``Engine`` has no per-token hook, so the driver wraps the jitted decode
callable of the engine it built: the engine appends the tokens of decode
step *s* to its requests just before it makes call *s + 1*, so the time
of that call is when those ids reached the host.  A request's last token,
where the wave's cache is full and no call follows it, is stamped when
the wave returns.  Every wave's stamps must equal its committed tokens,
so a change to the engine's loop fails the run instead of mis-timing it.

What the program runs, the kernel calls a wave makes and the operations
a token needs are the configuration's to state: its module
(``bench/configs/<config>.py``) holds the functions of ``CONFIG_NEEDS``,
and this driver knows no model.

Set-up draws the weights, builds the engine and serves one short wave at
each padded prompt length the traffic's cycle holds, so the window
compiles nothing.  Once the window has closed, one wave served in it (the
one with the longest request, ties drawn from the seed) is replayed
through the configuration's plain reference, teacher-forced with every
token the engine fed.  Per served token, the gap by which its logit lies
below the reference's best is taken; the mean over the wave
(``mean_logit_gap``) and the largest mean over one row of the wave
(``worst_row_logit_gap``, a fault confined to one slot) are each held to
the cell's limit.  With the control on, the configuration's control takes
the program's place in that comparison.

A window whose samples fall short of the mix's ``min_samples`` (the count
behind each tail) has no tail to report: the run fails.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from collections import deque
from typing import Dict, List

import numpy as np

from bench.lib import chip, stats
from bench.lib.outcome import Check, Context, Outcome
from bench.lib.traffic import ClosedLoop


#: the numbers compared, each with a limit in ``bench/limits``
GAP_CHECKS = {"mean_logit_gap": "mean", "worst_row_logit_gap": "worst_row"}

#: what a serving configuration's module holds: the program's config
#: fields, the weights, the wave's kernel calls, one token's operations
#: (read by ``model_step.mfu``) and the plain reference
CONFIG_NEEDS = ("program_fields", "make_params", "kernel_calls",
                "token_flops", "final_hidden", "head")


class StampMismatch(RuntimeError):
    """The engine committed tokens the driver did not see arrive."""


class TooFewSamples(RuntimeError):
    """The window holds fewer samples than the mix needs for its tails."""


def program_arch(cfg: Dict, ref):
    """The program's ``ArchConfig`` for a configuration file: the program's
    own config named by ``assumed.program_config``, with the fields that
    the configuration's module sets (it refuses what the program does not
    run)."""
    from repro.configs.base import get
    return dataclasses.replace(get(cfg["assumed"]["program_config"]),
                               **ref.program_fields(cfg))


def check_layout(arch, params) -> None:
    """The weights the benchmark drew have the program's own layout."""
    import jax

    from repro.models.model import build_model
    want = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError(f"weights {got} differ from the program's layout "
                         f"{want}")


class Stamps:
    """Wraps ``engine._decode``: per request of the current wave, the
    host time of each committed token, and the tokens each call fed."""

    def __init__(self, engine):
        self._decode = engine._decode
        engine._decode = self._call
        self.begin([])

    def begin(self, wave) -> None:
        self.wave = wave
        self.seen = [0] * len(wave)
        self.times: List[List[float]] = [[] for _ in wave]
        self.calls: List[float] = []
        self.fed: List = []

    def _stamp(self, now: float, last: bool) -> None:
        for i, r in enumerate(self.wave):
            k = len(r.out)
            new = k - self.seen[i]
            if new == 0:
                continue
            if new != 1 or (last and k != r.max_new):
                raise StampMismatch(
                    f"request {r.rid}: {new} tokens appeared between two "
                    f"decode calls (had {self.seen[i]}, now {k})")
            self.times[i].append(now)
            self.seen[i] = k

    def _call(self, params, cache, tokens, pos, pad_lens):
        self._stamp(time.perf_counter(), False)
        self.calls.append(time.perf_counter())
        self.fed.append(tokens)
        return self._decode(params, cache, tokens, pos, pad_lens)

    def end(self, now: float, committed: int) -> None:
        self._stamp(now, True)
        stamped = sum(map(len, self.times))
        if stamped != committed:
            raise StampMismatch(f"{stamped} tokens stamped, the wave "
                                f"committed {committed}")


def run(ctx: Context) -> Outcome:
    import jax
    from jax.profiler import TraceAnnotation

    from repro.serve.engine import Engine, Request

    cell, cfg = ctx.cell, ctx.cell.config
    ref = cell.module()
    arch = program_arch(cfg, ref)
    params = jax.block_until_ready(ref.make_params(cfg, ctx.seed))
    check_layout(arch, params)
    loop = ClosedLoop(cell.traffic, ctx.seed, arch.vocab)
    eng = Engine(arch, params, slots=loop.slots, max_len=loop.max_len,
                 dispatch=cfg["assumed"]["dispatch"])
    stamps = Stamps(eng)
    problems: List[str] = []

    for i, plen in enumerate(sorted(set(loop.wave_prompt_lens()))):
        warm = [Request(rid=-(j + 1), prompt=p, max_new=2)
                for j, p in enumerate(loop.warm_prompts(plen, i))]
        stamps.begin(warm)
        if eng.serve_wave(warm, deque(), {}) is None or eng.events:
            raise RuntimeError(f"warm-up wave failed: {eng.events}")

    if ctx.trace:
        chip.start_trace(ctx.trace_dir)
    ctx.counter.armed = True
    t0 = time.perf_counter()
    sent: Dict[int, float] = {}
    queue = deque()
    for k in range(loop.clients):
        queue.append(loop.request(k))
        sent[k] = t0
    next_k = loop.clients
    waves, requests = [], {}
    with TraceAnnotation("bench.window"):
        while True:
            batch = [queue.popleft() for _ in range(loop.slots)]
            reqs = [Request(rid=q.index, prompt=q.prompt, max_new=q.max_new)
                    for q in batch]
            stamps.begin(reqs)
            ws = time.perf_counter()
            with TraceAnnotation("bench.wave"):
                st = eng.serve_wave(reqs, deque(), {})
            we = time.perf_counter()
            plen = max(len(q.prompt) for q in batch)
            if st is None:
                problems.append(f"wave of requests {reqs[0].rid}.. failed")
            else:
                stamps.end(we, st.tokens)
            waves.append(dict(
                start=ws, end=we, plen=plen, rows=len(reqs),
                first_call=stamps.calls[0] if stamps.calls else we,
                decode_calls=len(stamps.calls),
                fed=stamps.fed,
                prompts=[q.prompt for q in batch],
                out=[list(r.out) for r in reqs],
                kernel_calls=ref.kernel_calls(cfg, len(reqs), plen,
                                              len(stamps.calls)),
                poison=None if st is None else int(st.moe_poison),
                failed=st is None))
            for r, ts in zip(reqs, stamps.times):
                requests[r.rid] = dict(
                    sent=sent[r.rid], prompt=len(r.prompt), times=ts,
                    bad=r.failed or r.truncated or len(r.out) != r.max_new
                    or any(not 0 <= t < arch.vocab for t in r.out))
            for _ in reqs:
                queue.append(loop.request(next_k))
                sent[next_k] = we
                next_k += 1
            if we >= t0 + ctx.seconds:
                break
    ctx.counter.armed = False
    if ctx.trace:
        jax.profiler.stop_trace()
    peak = chip.memory_peak_bytes(ctx.devs)
    events = list(eng.events)
    del eng, stamps
    gc.collect()

    # the window closes with the first wave that ends after --seconds, so
    # every wave in it is whole and no token or second is left out
    window = (t0, waves[-1]["end"])
    times = {k: r["times"] for k, r in requests.items()}
    tokens = sum(map(len, times.values()))
    # every tail a closed-loop mix can report; a cell's BENCHMARK.json
    # entries pick which, so a new mix needs no change here
    ttft = [(r["times"][0] - r["sent"]) * 1e3 for r in requests.values()
            if r["times"]]
    itl = [g * 1e3 for g in stats.token_gaps(times, window)]
    e2e = {"tok_s": tokens / (window[1] - window[0]),
           "setup_s": t0 - ctx.t_start}
    if ttft:
        e2e["ttft_p90_ms"] = stats.percentile(ttft, 90)
    if itl:
        e2e["itl_p95_ms"] = stats.percentile(itl, 95)

    samples = {"requests_ttft": len(ttft), "itl_gaps": len(itl),
               "tokens": tokens, "waves": len(waves)}
    for k, need in cell.traffic.get("min_samples", {}).items():
        if samples[k] < need:
            raise TooFewSamples(f"{samples[k]} {k} in the window; the mix "
                                f"needs {need} for its tails")

    bad = sum(r["bad"] for r in requests.values())
    checks = [Check("failed_requests", bad, 0),
              Check("engine_events", len(events), 0)]
    done = [w for w in waves if not w["failed"]]
    notes = {}
    if done:
        wave = pick_wave(done, ctx.seed)
        got = gaps(cfg, ref, params, loop.max_len, wave, ctx.control)
        notes = {"program": summary(got["gaps"], got["rows"]),
                 "dropped_dispatches": {"program": wave["poison"],
                                        "reference": got["dropped"]}}
        judged = notes["program"]
        if ctx.control:
            notes["control"] = summary(got["control_gaps"], got["rows"])
            judged = notes["control"]
        checks += [Check(name, judged[key],
                         float(ctx.limits[name]["limit"]))
                   for name, key in GAP_CHECKS.items()]
    else:
        problems.append("no wave completed")
    record = dict(window=window, waves=waves, requests=requests,
                  compiles=ctx.counter.programs, config=cfg)
    return Outcome(e2e, samples, checks, attempted=len(requests), failed=bad,
                   memory_peak_bytes=peak, record=record,
                   problems=problems, notes=notes)


def summary(gap: np.ndarray, rows: np.ndarray) -> Dict[str, float]:
    """The gaps of one wave's served tokens, in logits; ``rows`` is the
    wave row of each token."""
    per_row = np.bincount(rows, gap) / np.bincount(rows)
    return {"tokens": int(gap.size), "mean": float(np.mean(gap)),
            "worst_row": float(np.max(per_row)),
            "p99": float(np.percentile(gap, 99)),
            "max": float(np.max(gap)),
            "not_best": float(np.mean(gap > 0))}


def pick_wave(waves: List[Dict], seed: int) -> Dict:
    """The wave holding the longest served request; ties drawn from the
    seed."""
    longest = max(max(len(o) for o in w["out"]) for w in waves)
    tied = [w for w in waves if max(len(o) for o in w["out"]) == longest]
    rng = np.random.default_rng([int(seed) % (1 << 64), 5])
    return tied[int(rng.integers(len(tied)))]


def replay_inputs(wave: Dict):
    """The token matrix the engine ran (left-padded prompts, then each
    decode call's fed tokens) and the served (row, column, token)s."""
    b, plen = wave["rows"], wave["plen"]
    fed = np.concatenate([np.asarray(f) for f in wave["fed"]], 1) \
        if wave["fed"] else np.zeros((b, 0), np.int32)
    steps = max(len(o) for o in wave["out"]) - 1
    if fed.shape[1] < steps:
        raise StampMismatch(f"{fed.shape[1]} decode calls recorded for "
                            f"{steps + 1} served tokens")
    toks = np.zeros((b, plen + steps), np.int32)
    pad = np.zeros((b,), np.int32)
    rows, cols, served = [], [], []
    for i, (p, out) in enumerate(zip(wave["prompts"], wave["out"])):
        toks[i, plen - len(p):plen] = p
        pad[i] = plen - len(p)
        m = min(len(out), fed.shape[1])
        if list(fed[i, :m]) != list(out)[:m]:
            raise StampMismatch(f"row {i}: fed tokens differ from the "
                                "committed ones")
        for j, t in enumerate(out):
            rows.append(i)
            cols.append(plen - 1 + j)
            served.append(t)
    toks[:, plen:] = fed[:, :steps]
    return toks, pad, np.asarray(rows), np.asarray(cols), \
        np.asarray(served, np.int32)


def gaps(cfg: Dict, ref, params, max_len: int, wave: Dict,
         control: bool = False) -> Dict:
    """Per served token of one wave, the gap between the reference's best
    logit and the served token's (``gaps``) and its wave row (``rows``),
    and the dispatches the reference dropped; with ``control``, also the
    gap of the token the float8 control puts first at each of those
    positions."""
    import jax
    toks, pad, rows, cols, served = replay_inputs(wave)
    with jax.default_matmul_precision("highest"):
        h, dropped = ref.final_hidden(cfg, params, toks, pad, wave["plen"],
                                      max_len, rows, cols)
        best, picked, _ = ref.head(h, params["lm_head"], served)
        out = {"gaps": best - picked, "dropped": dropped, "rows": rows}
        if control:
            hl, _ = ref.final_hidden(cfg, params, toks, pad, wave["plen"],
                                     max_len, rows, cols, lowp=True)
            _, _, choice = ref.head(hl, params["lm_head"], served,
                                    lowp=True)
            del hl
            _, at_choice, _ = ref.head(h, params["lm_head"], choice)
            out["control_gaps"] = best - at_choice
    return out
