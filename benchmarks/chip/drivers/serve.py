"""Serving traffic: the program's ``Server.run`` under open-loop arrivals.

Set-up makes the weights from the seed on the device in the type they are
served in, builds one ``Server`` and warms it with one request per slot,
cycling through every prompt length of the mix, so every program the window
uses (each prefill length, decode, the slot merges) is compiled before it.

In the window a generator thread submits each request when it is due; the
main thread drives ``Server.run`` (which returns whenever the server runs
dry, and is called again at the next arrival). Each token is stamped on
the harness's side as the program appends it (``Request.out`` is a stamping
list). Time to first token runs from the request's due time; token gaps
are between consecutive stamps. A request still waiting at the window's end
counts with the window's end as its first token, and an open gap with the
window's end as its next stamp. The server then drains, unmeasured.

Once the window has closed and the program is freed, a seeded sample of
the finished requests, the longest among them, is run through the plain
reference over prompt and served tokens; the widest gap by which a served
(greedy) token's logit lies below the reference's best is compared.

Traffic keys: ``slots``, ``ctx``, ``rate_per_s``, ``prompt_lens``,
``output_lens``, ``check_requests``.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data, flops, program, weights
from chipbench.harness import Outcome, Session, StampList, load_module

DRAIN_S = 120.0


def build(s: Session):
    from repro.models import Backbone
    from repro.runtime.serve_loop import Server
    tr = s.cell.traffic
    pcfg = program.model_config(s.cell.config)
    bb = Backbone(pcfg, param_dtype=jnp.bfloat16, remat=False)
    params = weights.program_tree(bb.param_specs(), s.seed,
                                  s.cell.config["model_type"], jnp.bfloat16)
    return bb, params, Server(bb, params, slots=tr["slots"], ctx=tr["ctx"])


def warm(s: Session, srv) -> None:
    from repro.runtime.serve_loop import Request
    tr = s.cell.traffic
    rng = np.random.default_rng(s.seed)
    lens = tr["prompt_lens"]
    vocab = s.cell.config["vocab_size"]
    for i in range(max(tr["slots"], len(lens))):
        srv.submit(Request(rid=-1 - i, max_new=2, prompt=rng.integers(
            0, vocab, lens[i % len(lens)], dtype=np.int32)))
    srv.run()


def serve_window(s: Session, srv, arrivals: List[data.Arrival]):
    """Open-loop arrivals into ``Server.run``; returns the requests, the
    generator's worst lateness and the window's nominal end (s)."""
    from repro.runtime.serve_loop import Request
    reqs: List = []
    arrived = threading.Event()
    late = [0.0]

    def generate(start: float) -> None:
        for a in arrivals:
            wait = start + a.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            r = Request(rid=a.rid, prompt=a.prompt, max_new=a.max_new,
                        out=StampList())
            r.due = start + a.due
            late[0] = max(late[0], time.perf_counter() - r.due)
            reqs.append(r)
            srv.submit(r)
            arrived.set()

    with s.window():
        start = time.perf_counter()
        gen = threading.Thread(target=generate, args=(start,), daemon=True)
        gen.start()
        end = start + s.seconds
        while time.perf_counter() < end:
            arrived.clear()
            srv.run()
            arrived.wait(timeout=max(0.0, end - time.perf_counter()))
    gen.join()
    end = start + s.seconds
    deadline = time.perf_counter() + DRAIN_S
    while not all(r.done.is_set() for r in reqs) and \
            time.perf_counter() < deadline:
        srv.run()
        time.sleep(0.001)
    return reqs, late[0], end


def latencies(reqs, end: float):
    ttft, gaps = [], []
    for r in reqs:
        st = [t for t in r.out.stamps if t <= end]
        ttft.append((st[0] if st else end) - r.due)
        gaps.extend(np.diff(st).tolist())
        if st and len(st) < r.max_new:          # still decoding at the end
            gaps.append(end - st[-1])
    return ttft, gaps


def reference_gap(s: Session, reqs, prec: str = "f32") -> float:
    """Widest gap of a served token's logit below the reference's best."""
    cfg, tr = s.cell.config, s.cell.traffic
    done = [r for r in reqs if r.done.is_set()]
    rng = np.random.default_rng(np.random.SeedSequence([s.seed, 11]))
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.out), -r.rid))
    rest = [r for r in done if r is not longest]
    pick = [longest] + [rest[i] for i in rng.choice(
        len(rest), min(len(rest), tr["check_requests"] - 1), replace=False)]
    return served_gap(s, pick, prec)


def served_gap(s: Session, pick, prec: str) -> float:
    cfg, tr = s.cell.config, s.cell.traffic
    ref = load_module("refs", cfg["reference"])
    seqs = [jnp.asarray(np.concatenate([r.prompt, np.asarray(r.out[:-1],
                                                             np.int32)]))
            for r in pick]
    rows = [(len(r.prompt) - 1, len(r.out)) for r in pick]
    get = weights.leaf_fn(s.seed, cfg["model_type"], jnp.bfloat16)
    logits = ref.served_logits(get, cfg, seqs, rows, prec, tr["ctx"])
    worst = 0.0
    for r, lg in zip(pick, logits):
        lg = np.asarray(lg, np.float32)
        tok = np.asarray(r.out, np.int64)
        worst = max(worst, float((lg.max(-1) - lg[np.arange(len(tok)), tok]
                                  ).max()))
    return worst


def run(s: Session) -> Outcome:
    tr, cfg = s.cell.traffic, s.cell.config
    bb, params, srv = build(s)
    warm(s, srv)
    arrivals = data.serve_schedule(s.seed, s.seconds, tr["rate_per_s"],
                                   tr["prompt_lens"], tr["output_lens"],
                                   cfg["vocab_size"])
    calls: List[float] = []
    s.wrap(srv, "_prefill", "prefill")
    if s.trace:
        dec = srv._decode

        def decode(*a):
            calls.append(time.perf_counter())
            with s.span("decode"):
                return dec(*a)
        srv._decode = decode
    reqs, late, end = serve_window(s, srv, arrivals)
    s.read_memory()
    ttft, gaps = latencies(reqs, end)
    failed = sum(not r.done.is_set() for r in reqs)
    half = len(ttft) // 2
    print(f"[serve] {len(reqs)} requests, {len(gaps)} gaps, generator at "
          f"most {late * 1e3:.1f} ms late, {failed} unfinished after the "
          f"drain; median ttft of the first and second half of arrivals "
          f"{1e3 * np.median(ttft[:half]):.1f} / "
          f"{1e3 * np.median(ttft[half:]):.1f} ms; "
          f"{sum(len(r.out.stamps) == 0 or r.out.stamps[0] > end for r in reqs)}"
          f" waiting at the end", flush=True)
    e2e = {"ttft_p95_ms": 1e3 * data.percentile(ttft, 95),
           "token_gap_p99_ms": 1e3 * data.percentile(gaps, 99)}
    layer = {"decode_calls": calls, "requests": reqs, "wall_s": s.wall_s,
             "window": s.window_s}
    del srv, params, bb
    gc.collect()
    gap = reference_gap(s, reqs)
    checks = {"logit_gap": (gap, s.cell.limits["logit_gap"])}
    return Outcome(attempted=len(reqs), failed=failed, end_to_end=e2e,
                   checks=checks, layer=layer)
