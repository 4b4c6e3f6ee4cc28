"""Driver `serve_closed_latent`: `serve_closed`'s closed loop against a
server whose decoder attends over a LATENT cache in two algebraically equal
forms (`MLADecoder`: expanded for a prompt, absorbed for a step) and counts
its rows and its expert layers' work on the device. It stands on
`serve_closed_sparse`, whose `setup()` (`serve_closed_hybrid`'s: ONE order
of request shapes for every seed) and whose reference comparison a prompt
bucket (`_gaps`) it keeps, and overrides two things:

- `check()` holds EVERY token of each greedy probe to the plain reference
  (`benchmarks/families/deepseek_v3_serve.py`: the expanded form in
  float32) under a tolerance of its own: the prefill's first token and
  seven decoded in the absorbed form through latent rows that the expanded
  form wrote. The probes are served TOGETHER, so that rows grafted into the
  wrong slot, or the wrong half of a packed row, show: the seeded probe, one
  prompt of the mix's longest length (the other bucket's admit program) and
  the mix's first requests.
- the status snapshot also takes the decoder's `mla_*` and `moe_*`
  counters, whose change over the window the `latent_share` reader uses."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.drivers import serve_closed, serve_closed_sparse
from benchmarks.harness import traffic

COUNTER_KEYS = ("moe_pairs", "moe_expert_reads", "moe_pairs_max",
                "mla_rows_attended", "mla_rows_read")
BUSY_PROBES = serve_closed_sparse.BUSY_PROBES
#: each served probe token's reference logit may lie this far under the
#: reference's largest at its position. Readings on the chip under the
#: committed seeded draw (`models/deepseek_v3.py`: `q` and `o` drawn four
#: times larger than their neighbours, so that attention is a third of the
#: residual stream; PERF.md, Findings, PR 37, second session), at max
#: |logit| 4.2-4.8, 62-64 distinct tokens in a run's 64. The served path's
#: largest gap: 0.0281 over 15 sets of weights (960 probe tokens: near ties
#: that bfloat16 activations and latent rows flip; the next largest 0.0278,
#: 0.0264, 0.0177; under the first draw 0.0384 over 1152 tokens). Streams chosen by the reference and handed to this `check()`
#: by a stand-in server (`benchmarks/tests/test_rehearsal_latent.py -k
#: controls_fail`, on the chip) all came out NOT correct: one precision
#: below the configuration's (float8 wherever it has bfloat16: weights,
#: block inputs, the cached latent and rotary rows, the residual stream:
#: `reference_logits(lower=True)`) 0.190, with 4 of the 8 probes over 0.08;
#: the attention's output left out 0.695 (8 of 8 over), each latent row
#: one position late beside its rotary key 0.930 (8 of 8), the odd
#: positions never attended 0.391 (7 of 8): `reference_logits(fault=)`.
#: 0.08 lies between, 2.8 times the served path's largest and under half
#: of the smallest control's. A rounding flip passes; a precision dropped,
#: or attention over the wrong rows, does not
LOGIT_TOLERANCE = 0.08


class Driver(serve_closed_sparse.Driver):
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
            r["prompt"] for r in self.requests[:BUSY_PROBES - 2]]
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
            f"{float(gaps.max()):.5f}, tolerance {LOGIT_TOLERANCE}; max "
            f"|logit| {top:.3f}; {distinct} distinct tokens); served in "
            f"{t_b - t_a:.2f} s, reference in {t_c - t_b:.2f} s")
        if not (np.isfinite(gaps).all() and gaps.max() <= LOGIT_TOLERANCE):
            ok = False
        return ok and self._healthy()

    def _snapshot(self):
        """`serve_closed`'s, and the decoder's counters: the window's
        second snapshot leaves their change in the context, beside
        `status_delta`."""
        st = self.srv.status()
        now = {k: st.get(k) for k in COUNTER_KEYS}
        first = self.context.setdefault("latent_first", now)
        if first is not now and None not in now.values():
            self.context["latent_delta"] = {k: now[k] - first[k]
                                            for k in COUNTER_KEYS}
            self.notes.append(f"latent attention and expert layers over "
                              f"the window: {self.context['latent_delta']}")
        return {k: st[k] for k in serve_closed.STATUS_KEYS}
