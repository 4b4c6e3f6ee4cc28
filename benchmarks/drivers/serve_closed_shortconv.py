"""Driver `serve_closed_shortconv`: `serve_closed`'s closed loop against a
server whose decoder mixes gated short-convolution layers, which keep a
two-row tail a slot, with grouped-query attention layers, whose rows grow
with the rung (`Lfm2MoeDecoder`), and counts its attention rows and its
expert layers' work on the device. It is `serve_closed_window`'s driver
(ONE order of request shapes for every seed; `check()` holding EVERY token
of eight greedy probes, served together, to the plain reference a prompt
bucket at a time) with two things of its own:

- the tolerance `LOGIT_TOLERANCE` of its `check()`, against the plain
  reference of `benchmarks/families/lfm2_moe_serve.py` (the convolution a
  sum of its taps over a zero-padded sequence, a triangle by `where`,
  float32). Every prompt of the mix is longer than a tail, so every
  probe's tails were grafted from the prompt's last real positions and
  then shifted by each decoded token.
- the status snapshot takes the decoder's `moe_*` and
  `global_rows_attended` counters, whose change over the window the
  `shortconv_share` reader uses, under the context's key
  `shortconv_delta`."""
from __future__ import annotations

from benchmarks.drivers import serve_closed, serve_closed_window

COUNTER_KEYS = ("moe_pairs", "moe_expert_reads", "moe_pairs_max",
                "global_rows_attended")


class Driver(serve_closed_window.Driver):
    #: each served probe token's reference logit may lie this far under the
    #: reference's largest at its position. Readings on the chip under the
    #: committed seeded draw (`models/lfm2_moe.py`; PERF.md, section 6), at
    #: max |logit| 4.5-4.7 and 64 distinct tokens of a run's 64: the served
    #: path's largest gap 0.200 and 0.258 over two sets of weights (near
    #: ties that bfloat16 flips; the reference's own argmax at most
    #: positions). Streams chosen by the reference and handed to this
    #: `check()` by a stand-in server (`benchmarks/tests/
    #: test_rehearsal_shortconv.py -k controls_fail`, on the chip): one
    #: precision below the configuration's (float8 wherever it has bfloat16)
    #: 1.061, the taps one row late 5.448, the gates swapped 5.606: all NOT
    #: correct; the expert bias carried into the weights 0.003, correct (the
    #: seeded bias moves the weights by under 1 %: PERF.md, section 7). 0.6
    #: lies between, over twice the served path's largest and 0.46 under the
    #: smallest control that fails
    LOGIT_TOLERANCE = 0.6

    def _snapshot(self):
        """`serve_closed`'s, and the decoder's counters: the window's
        second snapshot leaves their change in the context, beside
        `status_delta`."""
        st = self.srv.status()
        now = {k: st.get(k) for k in COUNTER_KEYS}
        first = self.context.setdefault("shortconv_first", now)
        if first is not now and None not in now.values():
            self.context["shortconv_delta"] = {k: now[k] - first[k]
                                               for k in COUNTER_KEYS}
            self.notes.append(f"attention and expert layers over the "
                              f"window: {self.context['shortconv_delta']}")
        return {k: st[k] for k in serve_closed.STATUS_KEYS}
