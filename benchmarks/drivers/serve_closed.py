"""Driver `serve_closed`: a closed loop of clients against one
`GenerationServer`. Each client submits its next request when its last one
finished. Everything is read on the client's side of the public calls:
`submit()`, `on_token`, `result()`, `warmup()` and `status()`."""
from __future__ import annotations

import itertools
import os
import threading
import time

import numpy as np

from benchmarks.harness import stats, trace, traffic

#: counters of `GenerationServer.status()` whose change over the window the
#: per-layer readers use
STATUS_KEYS = ("steps", "admissions", "tokens", "token_fetches", "replays",
               "restarts", "errors")
PROBE_TOKENS = 8
#: the served first token's reference logit may lie this far under the
#: reference's largest: PR 22 measured 0.016 (at max |logit| 2.66) between
#: the kernel path and a dense float32 forward; a wrong mask or cache row
#: moves logits by far more
LOGIT_TOLERANCE = 0.05
RESULT_TIMEOUT_S = 120.0
#: after the window closes the run waits this long for requests in flight,
#: then shuts the server down; a request cut so is no failure if it had
#: begun to answer
GRACE_S = 3.0


def _stamp(times):
    """`on_token`: one client-clock stamp per token. A request's first
    token reaches the client right after the host fetched its admit
    program's result, which a traced run marks for the reduction."""
    if times:
        times.append(time.perf_counter())
    else:
        with trace.annotate("after:admit"):
            times.append(time.perf_counter())


class _Record:
    __slots__ = ("index", "want", "t_submit", "times", "tokens", "error")

    def __init__(self, index, want):
        self.index, self.want = index, want
        self.t_submit = None
        self.times = []          # one client-clock stamp per token
        self.tokens = None
        self.error = None


class Driver:
    def __init__(self, built, workload, seed, cache_dir, on_chip):
        self.built, self.workload = built, workload
        self.seed = int(seed)
        self.cache_dir = cache_dir
        self.on_chip = on_chip
        self.records = []
        self.notes = []
        # every admit program's result is followed by an `after:admit` mark
        self.context = {"marks": ["admit"]}

    # -- set-up ------------------------------------------------------------
    def setup(self):
        spec = self.workload["requests"]
        self.requests = traffic.make_requests(spec, self.built.vocab,
                                              self.seed)
        longest = max(r["max_new_tokens"] for r in self.requests)
        exec_dir = os.path.join(self.cache_dir, "exec",
                                self.workload["config"])
        self.srv = self.built.make_server(exec_dir, longest)

    def warm(self):
        w = self.srv.warmup()
        self.context["warm"] = w
        return w

    def check(self):
        """Before the window: the kernel is in the decode program (on the
        chip), and a greedy probe's first token is one the plain reference
        forward ranks within `LOGIT_TOLERANCE` of its best."""
        ok = True
        if self.on_chip:
            for rung in self.srv.cache_lengths:
                text = self.srv._store.lookup(
                    ("superstep", rung, self.srv.superstep)).call.as_text()
                if "tpu_custom_call" not in text:
                    self.notes.append(f"no tpu_custom_call in the decode "
                                      f"program of rung {rung}")
                    ok = False
        prompt = traffic.probe_prompt(self.workload["requests"],
                                      self.built.vocab, self.seed)
        t_a = time.perf_counter()
        toks = self.srv.submit(prompt, max_new_tokens=PROBE_TOKENS,
                               eos_id=None, method="greedy").result(
                                   timeout=RESULT_TIMEOUT_S)
        t_b = time.perf_counter()
        ref = self.built.reference_last_logits(prompt)
        t_c = time.perf_counter()
        gap = float(ref.max() - ref[toks[0]])
        self.notes.append(
            f"probe: prompt of {len(prompt)}, served first token {toks[0]}, "
            f"reference logit {ref[toks[0]]:.5f} against its largest "
            f"{ref.max():.5f} (gap {gap:.5f}, tolerance {LOGIT_TOLERANCE}); "
            f"served in {t_b - t_a:.2f} s, reference in {t_c - t_b:.2f} s")
        if not (np.isfinite(ref).all() and len(toks) == PROBE_TOKENS
                and gap <= LOGIT_TOLERANCE):
            ok = False
        return ok and self._healthy()

    def _healthy(self):
        st = self.srv.status()
        bad = [k for k in ("replays", "restarts", "errors") if st[k]]
        if bad or st["state"] != "serving":
            self.notes.append(f"server state {st['state']!r}, "
                              + ", ".join(f"{k}={st[k]}" for k in bad))
            return False
        return True

    # -- the measured run --------------------------------------------------
    def _client(self, take, stop):
        srv = self.srv
        while not stop.is_set():
            i = next(take)
            r = self.requests[i % len(self.requests)]
            rec = _Record(i, r["max_new_tokens"])
            times = rec.times
            rec.t_submit = time.perf_counter()
            self.records.append(rec)
            with trace.annotate("submit"):
                try:
                    handle = srv.submit(
                        r["prompt"], max_new_tokens=r["max_new_tokens"],
                        eos_id=None,
                        on_token=lambda _t, ts=times: _stamp(ts),
                        **r["kw"])
                except Exception as e:  # noqa: BLE001 — refused: a failure
                    rec.error = e
                    stop.wait(0.01)
                    continue
            with trace.annotate("idle-client"):
                try:
                    rec.tokens = handle.result(timeout=RESULT_TIMEOUT_S)
                except Exception as e:  # noqa: BLE001 — timeout or error
                    rec.error = e

    def _snapshot(self):
        st = self.srv.status()
        return {k: st[k] for k in STATUS_KEYS}

    def measure(self, seconds, tracer=None):
        """Warm traffic, then the window of `seconds`; returns the
        end-to-end values. With a tracer, a few seconds inside the window
        are traced (the values are then not the ones to report)."""
        stop = threading.Event()
        take = itertools.count()
        clients = [threading.Thread(target=self._client, args=(take, stop),
                                    daemon=True)
                   for _ in range(int(self.workload["clients"]))]
        for c in clients:
            c.start()
        time.sleep(float(self.workload["warmup_seconds"]))
        t0 = time.perf_counter()
        self.opened_wall = time.time()      # set-up ends here
        before = self._snapshot()
        t1 = t0 + seconds
        if tracer is not None:
            time.sleep(trace.trace_after(seconds))
            # every client is waiting for a reply unless it is in `submit`
            tracer.start(inside="idle-client")
            time.sleep(trace.TRACE_SECONDS)
            tracer.stop()
        time.sleep(max(0.0, t1 - time.perf_counter()))
        after = self._snapshot()
        stop.set()
        deadline = time.perf_counter() + GRACE_S
        for c in clients:
            c.join(max(0.0, deadline - time.perf_counter()))
        self.records = list(self.records)           # what the run saw
        self.cut = {id(r) for r in self.records
                    if r.tokens is None and r.error is None}
        self.healthy = self._healthy()
        self.srv.shutdown()                          # fails what is cut
        for c in clients:
            c.join(RESULT_TIMEOUT_S)
        self.window = (t0, t1)
        self.context["status_delta"] = {k: after[k] - before[k]
                                        for k in STATUS_KEYS}
        return self._end_to_end(t0, t1)

    def _end_to_end(self, t0, t1):
        tokens, gaps, ttft, rows = 0, [], [], []
        missed = 0
        for rec in self.records:
            ts = rec.times
            tokens += sum(1 for t in ts if t0 <= t < t1)
            gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:])
                        if t0 <= b < t1)
            if t0 <= rec.t_submit < t1:
                if ts and (rec.error is None or id(rec) in self.cut):
                    ttft.append((ts[0] - rec.t_submit) * 1e3)
                else:
                    missed += 1
                # cache rows the request held on average: its prompt plus
                # half of what it generated
                rows.append(len(self.requests[rec.index % len(
                    self.requests)]["prompt"]) + rec.want / 2.0)
        # a failed or refused request counts as the largest
        worst = max(ttft) if ttft else (t1 - t0) * 1e3
        ttft.extend([worst] * missed)
        self.context["mean_rows_in_use"] = (sum(rows) / len(rows)
                                            if rows else None)
        self.notes.append(f"ttft_p95_ms over {len(ttft)} requests submitted "
                          f"in the window ({missed} without a first token)")
        self.notes.append(f"tpot_p95_ms over {len(gaps)} token gaps; "
                          f"{tokens} tokens delivered in the window")
        out = {"serve_tokens_per_s": tokens / (t1 - t0)}
        if ttft:
            out["ttft_p95_ms"] = stats.percentile(ttft, 95)
        if gaps:
            out["tpot_p95_ms"] = stats.percentile(gaps, 95)
        return out

    # -- after the window --------------------------------------------------
    def counts(self):
        """(correct, attempted, failed): requests submitted in the window;
        every request of the whole run is checked for its length and ids.
        One still in flight when the run ended is held to having begun."""
        t0, t1 = self.window
        vocab = self.built.vocab
        attempted = failed = 0
        all_ok = True
        for rec in self.records:
            if id(rec) in self.cut:
                good = len(rec.times) >= 1
            else:
                good = (rec.error is None and rec.tokens is not None
                        and len(rec.tokens) == rec.want
                        and all(0 <= t < vocab for t in rec.tokens))
            all_ok = all_ok and good
            if t0 <= rec.t_submit < t1:
                attempted += 1
                failed += not good
        self.notes.append(f"{len(self.records)} requests in the whole run, "
                          f"{len(self.cut)} still in flight at its end")
        return bool(all_ok and self.healthy), attempted, failed
