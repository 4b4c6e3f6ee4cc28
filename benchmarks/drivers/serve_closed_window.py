"""Driver `serve_closed_window`: `serve_closed`'s closed loop against a
server whose decoder has layers of two kinds with caches of two lengths
(`AfmoeDecoder`: sliding-window layers on a ring of the window's rows,
full-attention layers on rows that grow with the rung) and counts their
rows and its expert layers' work on the device. It stands on
`serve_closed_sparse`, whose `setup()` (`serve_closed_hybrid`'s: ONE order
of request shapes for every seed) and whose reference comparison a prompt
bucket (`_gaps`) it keeps, and brings two things of its own:

- `check()` holds EVERY token of each greedy probe to the plain reference
  (`benchmarks/families/afmoe_serve.py`: a band and a triangle by `where`,
  float32) under the tolerance `LOGIT_TOLERANCE`, a class attribute: the
  prefill's first token (the band kernel's and the ring graft's) and seven
  decoded against the rings and the full leaves. The probes are served
  TOGETHER, so that rows grafted into the wrong slot or the wrong ring row
  show: the seeded probe, one prompt of the mix's longest length (the other
  bucket's admit program) and the mix's first requests. Every prompt of the
  mix is longer than the window, so every probe's rings have wrapped.
- the status snapshot also takes the decoder's `window_*`, `global_*` and
  `moe_*` counters, whose change over the window the `window_share` reader
  uses, under the context's key `window_delta`."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.drivers import serve_closed, serve_closed_sparse
from benchmarks.harness import traffic

COUNTER_KEYS = ("moe_pairs", "moe_expert_reads", "moe_pairs_max",
                "window_rows_attended", "global_rows_attended")


class Driver(serve_closed_sparse.Driver):
    #: each served probe token's reference logit may lie this far under the
    #: reference's largest at its position. Readings on the chip under the
    #: committed seeded draw (`models/afmoe.py`; PERF.md, section 6),
    #: at max |logit| 4.3-4.7, 62-63 distinct tokens of a run's 64: the
    #: served path's largest gap 0.398 over 9 sets of weights (576 probe
    #: tokens; 0.108-0.398 a set, median 0.248: near ties that bfloat16
    #: flips, mostly in the router's choice of 8 of 128, which the
    #: post-norms then carry to the stream at full weight; the reference's
    #: own argmax at the rest). Streams chosen by the reference and handed
    #: to this `check()` by a stand-in server (`benchmarks/tests/
    #: test_rehearsal_window.py -k controls_fail`, on the chip) all came out
    #: NOT correct: one precision below the configuration's (float8 wherever
    #: it has bfloat16) 2.017, 8 of 8 probes over 1.0; the sliding layers
    #: attending every earlier position 3.873 (8 of 8 over 2.5); the full
    #: layers rotated 1.291 (8 of 8 over 0.3); the gate left out 2.397 (8 of
    #: 8 over 1.8). 0.6 lies between, 1.5 times the served path's largest
    #: and under half of the smallest control's
    LOGIT_TOLERANCE = 0.6

    def check(self):
        """Before the window: the kernels are in the decode program (on the
        chip), the server is healthy, and every token of every greedy probe
        ranks within `LOGIT_TOLERANCE` of the reference's best at its
        position."""
        ok = True
        if self.on_chip:
            for rung in self.srv.cache_lengths:
                text = self.srv._store.lookup(
                    ("superstep", rung, self.srv.superstep)).call.as_text()
                if "tpu_custom_call" not in text:
                    self.notes.append(f"no tpu_custom_call in the decode "
                                      f"program of rung {rung}")
                    ok = False
        spec = self.workload["requests"]
        probe = traffic.probe_prompt(spec, self.built.vocab, self.seed)
        longest = int(spec["prompt_len"]["hi"])
        prompts = [probe, np.resize(probe[::-1], longest)] + [
            r["prompt"] for r in
            self.requests[:serve_closed_sparse.BUSY_PROBES - 2]]
        n = serve_closed.PROBE_TOKENS
        t_a = time.perf_counter()
        handles = [self.srv.submit(p, max_new_tokens=n, eos_id=None,
                                   method="greedy") for p in prompts]
        streams = [h.result(timeout=serve_closed.RESULT_TIMEOUT_S)
                   for h in handles]
        t_b = time.perf_counter()
        if any(len(s) != n for s in streams):
            self.notes.append(f"probe streams of {list(map(len, streams))} "
                              f"tokens, {n} asked")
            return False
        gaps, top = self._gaps(prompts, streams)
        t_c = time.perf_counter()
        distinct = len({t for s in streams for t in s})
        self.notes.append(
            f"probe: prompt of {len(probe)}, served tokens "
            f"{list(streams[0])}, gaps to the reference's largest logit "
            f"{[round(float(g), 5) for g in gaps[0]]}; {len(prompts)} probes "
            f"at once, prompts of {[len(p) for p in prompts]}, largest gap a "
            f"probe {[round(float(g), 5) for g in gaps.max(-1)]} (largest "
            f"{float(gaps.max()):.5f}, tolerance {self.LOGIT_TOLERANCE}; max "
            f"|logit| {top:.3f}; {distinct} distinct tokens); served in "
            f"{t_b - t_a:.2f} s, reference in {t_c - t_b:.2f} s")
        if not (np.isfinite(gaps).all()
                and gaps.max() <= self.LOGIT_TOLERANCE):
            ok = False
        return ok and self._healthy()

    def _snapshot(self):
        """`serve_closed`'s, and the decoder's counters: the window's
        second snapshot leaves their change in the context, beside
        `status_delta`."""
        st = self.srv.status()
        now = {k: st.get(k) for k in COUNTER_KEYS}
        first = self.context.setdefault("window_first", now)
        if first is not now and None not in now.values():
            self.context["window_delta"] = {k: now[k] - first[k]
                                            for k in COUNTER_KEYS}
            self.notes.append(f"window and full attention and expert "
                              f"layers over the window: "
                              f"{self.context['window_delta']}")
        return {k: st[k] for k in serve_closed.STATUS_KEYS}
