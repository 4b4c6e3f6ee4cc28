"""Driver `serve_closed_sparse`: `serve_closed`'s closed loop against a
server whose decoder attends over the cache rows a learned indexer selects
and counts that selection on the device (`KeyeDecoder`). Over
`serve_closed_hybrid`, whose `setup()` it keeps (ONE order of request
shapes for every seed: at 32 slots and turns of a third of the window,
WHICH requests a window serves would otherwise be the seed's), it overrides
two things:

- `check()` holds EVERY token of each greedy probe to the plain reference:
  the prefill's first token and seven decoded through rows that the
  indexer selected out of a context of 4096 and more (every prompt of the
  mix is longer than `topk`). The probes are served TOGETHER, so that rows
  grafted into the wrong slot or gathered from the wrong one show: the
  seeded probe (the mix's shortest length), one prompt of the mix's longest
  length (the other bucket's admit program) and the mix's first requests.
  The reference (`benchmarks/families/keye_vl_serve.py`) runs once a prompt
  bucket, over the probes of that bucket padded on the right (the model is
  causal), and hands back the logits at the probes' eight positions alone.
- the status snapshot also takes the decoder's `dsa_*` and `moe_*`
  counters, whose change over the window the `sparse_share` reader uses."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.drivers import serve_closed, serve_closed_hybrid
from benchmarks.harness import traffic

COUNTER_KEYS = ("moe_pairs", "moe_expert_reads", "moe_pairs_max",
                "dsa_rows_scored", "dsa_rows_selected")
#: requests served at once by `check()`: the probe, the longest prompt and
#: the mix's first ones
BUSY_PROBES = 8
#: each served probe token's reference logit may lie this far under the
#: reference's largest at its position. Two readings on the chip (PERF.md,
#: Findings, PR 35), at max |logit| 4.2-4.6 and a median distance of 0.15
#: between the reference's two best: the served path's largest gap was
#: 0.0208 over 512 probe tokens (8 sets of weights, 64 tokens each; 506 of
#: them ARE the reference's argmax, the rest near ties that bfloat16
#: activations and cache rows flip); streams chosen by the reference one
#: precision below the configuration's (float8 wherever it has bfloat16,
#: bfloat16 index sums: `reference_logits(lower=True)`), handed to this
#: `check()` by a stand-in server (the control in
#: `benchmarks/tests/test_rehearsal_sparse.py`, run on the chip), came out
#: NOT correct. 0.06 lies between, three times the first reading: a
#: rounding flip passes; a wrong cache row, a selection from the wrong
#: slot or a precision dropped moves logits by more (the indexer's
#: selection alone moves these logits by 0.06 rms and decides one argmax
#: in seven). A control of float8 WEIGHTS alone read 0.025 and passed: the
#: seeded logits are four fifths the last token's embedding through the
#: head, which float8 weights move by 0.03
LOGIT_TOLERANCE = 0.06


class Driver(serve_closed_hybrid.Driver):
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

    def _gaps(self, prompts, streams):
        """(gaps (probes, n): how far each served token's reference logit
        lies under the reference's largest at its position; the largest
        |logit| seen). One reference program a prompt bucket."""
        n = len(streams[0])
        buckets = sorted(self.srv.prompt_buckets)
        gaps = np.zeros((len(prompts), n), np.float32)
        top = 0.0
        by_bucket = {}
        for i, p in enumerate(prompts):
            by_bucket.setdefault(
                next(b for b in buckets if b >= len(p)), []).append(i)
        for bucket, rows in sorted(by_bucket.items()):
            # position plen - 1 + i of a row's forward predicts its token i
            ids = np.zeros((len(rows), bucket + n - 1), np.int32)
            at = np.zeros((len(rows), n), np.int32)
            for row, where, i in zip(ids, at, rows):
                p, s = prompts[i], streams[i]
                row[:len(p) + n - 1] = np.concatenate([p, s[:-1]])
                where[:] = len(p) - 1 + np.arange(n)
            ref = self.built.reference_logits(ids, at=at)  # (rows, n, V)
            served = np.asarray([streams[i] for i in rows])
            gaps[rows] = ref.max(-1) - np.take_along_axis(
                ref, served[..., None], -1)[..., 0]
            top = max(top, float(np.abs(ref).max()))
        return gaps, top

    def _snapshot(self):
        """`serve_closed`'s, and the decoder's counters: the window's
        second snapshot leaves their change in the context, beside
        `status_delta`."""
        st = self.srv.status()
        now = {k: st.get(k) for k in COUNTER_KEYS}
        first = self.context.setdefault("sparse_first", now)
        if first is not now and None not in now.values():
            self.context["sparse_delta"] = {k: now[k] - first[k]
                                            for k in COUNTER_KEYS}
            self.notes.append(f"indexer and expert layers over the window: "
                              f"{self.context['sparse_delta']}")
        return {k: st[k] for k in serve_closed.STATUS_KEYS}
