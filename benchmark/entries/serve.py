"""A serving cell: the program's ``KerasServer`` with a token model, and a
closed loop of clients of the benchmark's own that speak the server's line
protocol with ``"stream": true`` and stamp every partial line as it
arrives. Each client sends its next request when its last is done.

Set-up makes the weights from the seed, hands the network to the server,
warms the prefill buckets the mix uses (the engine warms its own decode
ladder) and fills the rows; the window opens when every client has had a
first token. When it closes no new request is sent; those in flight are
waited for, up to ``drain_seconds``. Then the server is drained, its state
freed, and a sample of the finished requests, drawn from the seed with the
longest in it, is run through the plain reference: the widest gap by which
a served token's logit lies below the reference's best is what ``correct``
compares.
"""

from __future__ import annotations

import gc
import json
import socket
import threading
import time

import numpy as np

from benchmark import compare, program, traffic

MODEL_KEY = "benchmark-model.zip"     # a name, not a file: see serve_model


def serve_model(srv, net) -> None:
    """Hand the server the network under ``MODEL_KEY`` as its loader would
    have left it, without writing 0.5 GB to disk and reading it back in
    every run (``PERF.md``, Open questions)."""
    with srv._state_lock:
        srv._models[MODEL_KEY] = net
        srv._last = MODEL_KEY


class Client(threading.Thread):
    """One connection, one request at a time."""

    def __init__(self, host, port, take, log, stop, first_share=1.0):
        super().__init__(daemon=True)
        self.take, self.log, self.stop_flag = take, log, stop
        self.first_share = first_share
        self.sock = socket.create_connection((host, port))
        self.file = self.sock.makefile("rwb")
        self.first_token = threading.Event()

    def request(self, req: dict) -> dict:
        import jax
        rec = {"prompt": req["tokens"], "want": req["max_new_tokens"],
               "stamps": [], "tokens": [], "error": None}
        line = (json.dumps({"op": "generate", "model": MODEL_KEY,
                            "stream": True, **req}) + "\n").encode()
        rec["sent"] = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:client_send"):
            self.file.write(line)
            self.file.flush()
        while True:
            with jax.profiler.TraceAnnotation("bench:client_recv_wait"):
                raw = self.file.readline()
            now = time.perf_counter()
            if not raw:
                rec["error"] = "server closed the connection"
                break
            resp = json.loads(raw)
            if isinstance(resp, dict) and resp.get("partial"):
                rec["stamps"].append(now)
                rec["tokens"].append(int(resp["t"]))
                self.first_token.set()
                continue
            rec["done"] = now
            if "error" in resp:
                rec["error"] = str(resp["error"])
            elif list(resp.get("tokens", [])) != rec["tokens"]:
                rec["error"] = "the final answer differs from the stream"
            break
        self.first_token.set()
        return rec

    def run(self):
        try:
            share = self.first_share
            while not self.stop_flag.is_set():
                req = self.take()
                if req is None:
                    break
                if share < 1.0:     # the first round ends at spread-out times
                    req = dict(req, max_new_tokens=max(
                        2, int(req["max_new_tokens"] * share)))
                    share = 1.0
                self.log.append(self.request(req))
        finally:
            self.first_token.set()
            self.file.close()
            self.sock.close()


def run(ctx) -> dict:
    import jax

    from deeplearning4j_tpu.keras.server import KerasServer

    cfg, mix = ctx.cfg, ctx.mix
    if "matmul_precision" in cfg:
        # what the configuration states of its products; without the key
        # the chip's default holds, one bfloat16 pass on a v5e
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
    weights = ctx.reference.make_weights(cfg, ctx.seed)
    net = program.build_net(cfg, weights)
    del weights
    pool = traffic.serve_requests(cfg, mix, ctx.seed)
    if "compile_cache" in mix:
        # the program's own knob (keras/batching.py): its default budget of
        # 512 MiB counts an executable's scratch, which one GPT-2 decode
        # program exceeds alone, so prefill and decode evict each other and
        # are compiled again at every switch (PERF.md, Open questions)
        from deeplearning4j_tpu.keras import batching
        batching.set_compile_cache(batching.CompileCache(
            **mix["compile_cache"]))
    srv = KerasServer(**mix["server"])
    serve_model(srv, net)

    lock, cursor, log, stop = threading.Lock(), [0], [], threading.Event()

    def take():
        with lock:
            if cursor[0] >= len(pool):
                return None         # the pool is sized never to run out
            cursor[0] += 1
            return pool[cursor[0] - 1]

    try:
        # warm-up: one prompt of every length the mix can send, so that
        # each prefill bucket and each eager page write is compiled
        warm = Client(srv.host, srv.port, take, [], stop)
        prefix = pool[0]["tokens"][:mix["shared_prefix_tokens"]]
        for n in traffic.serve_lengths(mix):
            rec = warm.request({"tokens": prefix + [1] * (n - len(prefix)),
                                "max_new_tokens": 2})
            if rec["error"]:
                raise RuntimeError(f"warm-up request failed: {rec['error']}")
        warm.file.close()
        warm.sock.close()

        # each client's first answer is cut to a share of its length of its
        # own, so that the rows do not all end together: a loop that has
        # run for long has its rows at all stages
        clients = [Client(srv.host, srv.port, take, log, stop,
                          first_share=(i + 1) / mix["clients"])
                   for i in range(mix["clients"])]
        for c in clients:
            c.start()
        for c in clients:
            c.first_token.wait()        # the rows are full

        def watch(seconds):
            """Let the loop run for ``seconds``; what the program counted."""
            compiled = lambda: (program.counter("jax_compile_total")
                                + srv._gen.stats()["compiles"])
            compiles, steps = compiled(), program.counter(
                "serving_decode_steps_total")
            t0, pages = time.perf_counter(), []
            while time.perf_counter() - t0 < seconds:
                time.sleep(0.25)
                s = srv._gen.stats()
                pages.append(s["kv_pages_used"] / max(1, s["kv_pages_total"]))
            return {"t0": t0, "t1": time.perf_counter(),
                    "decode_steps": program.counter(
                        "serving_decode_steps_total") - steps,
                    "compiles_in_window": compiled() - compiles,
                    "kv_pages_used_share": float(np.mean(pages))}

        ctx.open_window()
        seen = watch(ctx.window_seconds)
        traced = None
        if ctx.trace:
            with ctx.traced():
                traced = watch(ctx.trace_seconds)
        stop.set()
        t1 = (traced or seen)["t1"]
        for c in clients:
            c.join(max(0.0, t1 + mix["drain_seconds"] - time.perf_counter()))
        hung = sum(c.is_alive() for c in clients)
        peak = ctx.memory_peak_bytes()
    finally:
        stop.set()
        srv.drain(grace_s=5.0)

    records = list(log)
    t0, t1 = seen.pop("t0"), seen.pop("t1")
    m = {**window_measures(records, t0, t1, mix), **seen}
    if traced:
        m["traced"] = {**window_measures(
            records, traced.pop("t0"), traced.pop("t1"), mix), **traced}
    in_window = lambda r: t0 <= r["sent"] < t1
    attempted = hung + sum(1 for r in records if in_window(r))
    failed = hung + sum(1 for r in records if r["error"] and in_window(r))

    # free the program's state, then the reference over a sample
    del net, srv, clients
    gc.collect()
    done = [r for r in records if not r["error"] and r["tokens"]]
    sample = pick_sample(done, mix["check_requests"], ctx.seed)
    gaps = reference_gaps(ctx, sample)
    numbers = compare.serving_numbers([g for g, _ in gaps],
                                      mix["check_requests"])
    numbers["sample_tokens"] = int(sum(len(g) for g, _ in gaps))
    extras = {}
    if "control" in ctx.extra:      # tools/readings.py alone asks for it
        low = reference_gaps(ctx, sample, cfg["control_precision"])
        extras["control"] = compare.serving_numbers(
            [g for _, g in low], mix["check_requests"])
    return {
        "end_to_end": {
            "serve_out_tokens_per_s": m["out_tokens"] / m["window_s"]},
        "measures": m, "attempted": attempted, "failed": failed,
        "numbers": numbers, "extras": extras, "memory_peak_bytes": peak,
    }


def window_measures(records, t0, t1, mix) -> dict:
    """What the clients saw, reduced: tokens received inside the window,
    the time to first token of every request sent inside it (one that never
    got a token counts the whole wait until it was given up), every gap
    between successive tokens that closed inside it."""
    out_tokens = context = 0
    gaps, ttft, prompt_tokens = [], [], 0
    for r in records:
        inside = [t for t in r["stamps"] if t0 <= t <= t1]
        out_tokens += len(inside)
        base = len(r["prompt"])
        context += sum(base + i for i, t in enumerate(r["stamps"])
                       if t0 <= t <= t1)
        gaps += [b - a for a, b in zip(r["stamps"], r["stamps"][1:])
                 if t0 <= b <= t1]
        if r["stamps"] and t0 <= r["stamps"][0] <= t1:
            prompt_tokens += base
        if t0 <= r["sent"] < t1:
            first = r["stamps"][0] if r["stamps"] else r.get(
                "done", t1 + mix["drain_seconds"])
            ttft.append(first - r["sent"])
    p95 = lambda xs: float(np.percentile(xs, 95)) * 1e3 if xs else None
    return {"window_s": t1 - t0, "out_tokens": out_tokens,
            "context_tokens": context, "prompt_tokens": prompt_tokens,
            "requests_sent": len(ttft), "gaps": len(gaps),
            "ttft_p95_ms": p95(ttft), "itl_p95_ms": p95(gaps)}


def pick_sample(done: list, n: int, seed: int) -> list:
    """``n`` finished requests drawn from the seed, the longest among them."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i]["prompt"])
                  + len(done[i]["tokens"]))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng(seed)
    picks = rng.choice(rest, size=min(n - 1, len(rest)), replace=False)
    return [done[longest]] + [done[i] for i in picks]


def reference_gaps(ctx, sample, precision="float32") -> list:
    import jax
    with jax.default_matmul_precision("highest"):
        weights = ctx.reference.make_weights(ctx.cfg, ctx.seed)
        return ctx.reference.served_gaps(
            ctx.cfg, weights, [(r["prompt"], r["tokens"]) for r in sample],
            precision=precision)
