#!/usr/bin/env python3
"""First contact with the chip: the two main paths, through the public
entry points, at the full width of ResNet-50 and BERT-base.

    python3 chip_smoke.py             # one chip: phases `train` and `serve`
    python3 chip_smoke.py --chips 4   # four chips: the dp phase only

- `train`: `models.zoo.ResNet50(...).init()` then the public `fit()` on
  seeded synthetic ImageNet-shaped batches (batch 128).
- `serve`: BERT-base behind `GenerationServer`, dense-cache and paged, six
  mixed requests each; a third server warm-starts from the first one's
  on-disk executables; one stream is compared with the dense-attention
  reference.
- `--chips 4`: `ParallelWrapper.Builder(net).workers(4)` beside the same
  steps through plain `fit()` on one chip. Runs no other phase.

One process, no child: a chip belongs to one process at a time. The script
refuses to run anywhere but on a TPU (off the chip every kernel would pick
its interpreter and every caller its dense reference, and all phases would
pass without touching what they are here to check). Any failed check raises;
nothing is caught on the way to the result.

Everything printed is smoke output, not a benchmark. The LAST stdout line is
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and is printed only when every phase passed. Details go to earlier lines
and to chiprun_out/chip_smoke/report_chips<N>.json.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


# ---------------------------------------------------------------- train ----
def _repeated_batches(batch, steps, hw, classes, seed):
    """One seeded ImageNet-shaped batch served `steps` times: a repeated
    batch is what makes 'the loss went down' a fair check on random data."""
    from deeplearning4j_tpu.datasets.iterators import (
        DataSetIterator, SyntheticImageNetIterator)

    class Repeated(DataSetIterator):
        def __init__(self):
            super().__init__(batch)
            self._ds = SyntheticImageNetIterator(
                batch, num_examples=batch, height=hw, width=hw,
                num_classes=classes, seed=seed).next()

        def numExamples(self):
            return steps * batch

        def next(self, num=None):
            self._check_has_next()
            self._cursor += batch
            return self._ds

    return Repeated()


class _StepClock:
    """TrainingListener: per-step loss (a host sync — this is a smoke run,
    not a timing) and wall time since the previous step."""

    def __init__(self):
        self.losses, self.seconds = [], []
        self._t = time.perf_counter()

    def iterationDone(self, model, iteration, epoch):
        self.losses.append(float(model.score()))
        now = time.perf_counter()
        self.seconds.append(now - self._t)
        self._t = now


def _resnet50(hw, classes):
    """The zoo model at its defaults but for the learning rate: the zoo's
    Nesterovs(0.1, 0.9) has no warm-up and its first steps on a repeated
    random batch climb before they fall (8.7 -> 29 -> 53 -> 17 at a small
    size on the CPU), which would make 'the loss fell' a coin toss over a
    handful of steps. 0.01 descends from the first step."""
    from deeplearning4j_tpu.models.zoo import ResNet50
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    return ResNet50(numClasses=classes, dataType="bfloat16",
                    inputShape=(hw, hw, 3),
                    updater=Nesterovs(0.01, 0.9)).init()


def _train_resnet50(batch, steps, hw, classes, seed, trainer=lambda n: n):
    """`steps` steps on one repeated batch through `trainer(net).fit(...)`;
    returns the net and its step clock once every loss is finite and the
    last is below the first."""
    import math
    net = _resnet50(hw, classes)
    clock = _StepClock()
    net.setListeners(clock)
    trainer(net).fit(_repeated_batches(batch, steps, hw, classes, seed))
    losses = clock.losses
    check(len(losses) == steps, f"{steps} steps ran, got {len(losses)}")
    check(all(math.isfinite(v) for v in losses),
          f"every loss finite: {losses}")
    check(losses[-1] < losses[0],
          f"loss fell on a repeated batch: {losses[0]} -> {losses[-1]}")
    return net, clock


def phase_train(batch=128, steps=7, hw=224, classes=1000, seed=0):
    """ResNet-50 through the public fit(): `steps` steps, the first of
    which compiles."""
    import jax

    net, clock = _train_resnet50(batch, steps, hw, classes, seed)
    dev = jax.devices()[0]
    leaves = jax.tree_util.tree_leaves(net._params)
    check(leaves and all(l.devices() == {dev} for l in leaves),
          f"every parameter lives on {dev}")
    out = {"first_step_s_incl_compile": round(clock.seconds[0], 2),
           "later_step_s": [round(s, 4) for s in clock.seconds[1:]],
           "losses": [round(v, 4) for v in clock.losses],
           "param_leaves": len(leaves), "param_device": str(dev)}
    say(f"train: ResNet-50 batch={batch} hw={hw} steps={steps} "
        f"first step (compile + run) {out['first_step_s_incl_compile']} s, "
        f"later steps {out['later_step_s']} s, losses {out['losses']}, "
        f"{len(leaves)} parameter arrays on {dev}")
    return out


# ---------------------------------------------------------------- serve ----
def _requests(prompt_lens, vocab, seed):
    """Seeded prompts; even ones greedy, odd ones sampled."""
    import numpy as np
    rng = np.random.default_rng(seed)
    reqs = []
    for i, n in enumerate(prompt_lens):
        kw = ({"method": "greedy"} if i % 2 == 0 else
              {"method": "sample", "temperature": 0.8, "top_k": 40})
        reqs.append((rng.integers(1, vocab, n).astype(np.int32), kw))
    return reqs


def _serve(srv, reqs, new_tokens, timeout):
    handles = [srv.submit(p, max_new_tokens=new_tokens, eos_id=None, **kw)
               for p, kw in reqs]
    streams = [h.result(timeout=timeout) for h in handles]
    check(all(len(s) == new_tokens for s in streams),
          f"every request completed with {new_tokens} tokens: "
          f"{[len(s) for s in streams]}")
    st = srv.status()
    check(st["replays"] == 0 and st["restarts"] == 0 and st["errors"] == 0,
          f"no replay, restart or error: replays={st['replays']} "
          f"restarts={st['restarts']} errors={st['errors']}")
    check(st["state"] == "serving", f"server state {st['state']!r}")
    return streams


def _kernel_in_decode(srv, expect, what):
    """{rung: is the Pallas kernel (`tpu_custom_call`) in the compiled text
    of that rung's decode-step executable} — read from the entries the
    server's store holds, not from a flag; checked against `expect`
    (None: reported only, for a rehearsal off the chip)."""
    found = {rung: "tpu_custom_call" in srv._store.lookup(
        ("superstep", rung, srv.superstep)).call.as_text()
        for rung in srv.cache_lengths}
    if expect is not None:
        check(all(v == expect for v in found.values()),
              f"{what}: tpu_custom_call in the decode executables by rung "
              f"should be {expect}: {found}")
    return found


def phase_serve(out_dir, cfg=None, slots=8, cache_lengths=(128, 512),
                prompt_buckets=(32, 512),
                prompt_lens=(16, 40, 90, 150, 220, 300), new_tokens=32,
                page_size=16, seed=0, timeout=600.0, expect_kernel=True):
    """BERT-base (or `cfg`) behind GenerationServer: dense-cache, paged,
    warm-from-disk, and the dense-attention reference. `expect_kernel`
    is False only where a rehearsal runs this off the chip.

    Two prompt buckets, not the server's default ladder of seven: every
    (rung, bucket) pair is one 12-layer executable that takes the chip's
    compiler about 25 s (measured compiling for a described v5e), and
    the whole run has to fit its time limit cold. That is 8 executables
    a server, 5 of them whole-model programs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.generation.decode import BertDecoder
    from deeplearning4j_tpu.generation.server import GenerationServer
    from deeplearning4j_tpu.models.bert import (bert_base, bert_encode,
                                                bert_mlm_logits,
                                                init_bert_params)

    cfg = cfg or bert_base()
    params = init_bert_params(cfg, jax.random.PRNGKey(seed))
    reqs = _requests(prompt_lens, cfg.vocab_size, seed)
    greedy = [i for i, (_, kw) in enumerate(reqs) if kw["method"] == "greedy"]
    kernel = True if expect_kernel else None
    exec_dir = os.path.join(out_dir, "exec")
    shutil.rmtree(exec_dir, ignore_errors=True)   # the first server is cold
    out = {}

    def server(decoder, sub, **kw):
        kw.setdefault("cache_lengths", list(cache_lengths))
        kw.setdefault("prompt_buckets", list(prompt_buckets))
        return GenerationServer(
            decoder, slots=slots, max_new_tokens=new_tokens, seed=seed,
            exec_cache_dir=os.path.join(exec_dir, sub), **kw)

    try:
        # -- the kernel's numbers against the dense reference, one prompt --
        ids = jnp.asarray(reqs[0][0])[None]
        logits = {impl: np.asarray(jax.jit(
            lambda p, x, impl=impl: bert_mlm_logits(cfg, p, bert_encode(
                cfg, p, x, causal=True, attn_impl=impl)))(params, ids),
            np.float32) for impl in ("auto", "dense")}
        check(all(np.isfinite(v).all() for v in logits.values()),
              "every logit finite")
        scale = float(np.abs(logits["dense"]).max())
        gap = float(np.abs(logits["auto"] - logits["dense"]).max())
        check(logits["auto"].shape == (1, len(reqs[0][0]), cfg.vocab_size),
              f"logits shape {logits['auto'].shape}")
        check(gap <= 0.05 * max(1.0, scale),
              f"causal forward, attn_impl auto vs dense: max|dlogit| {gap} "
              f"against max|logit| {scale}")
        out["logits_auto_vs_dense"] = {"max_abs_diff": gap, "max_abs": scale}
        say(f"serve: causal forward logits finite, auto vs dense "
            f"max|diff| {gap:.3g} (max|logit| {scale:.3g})")

        # -- dense-cache server (cold store) -------------------------------
        dense = server(BertDecoder(cfg, params), "dense")
        w = dense.warmup()
        check(w["from_disk"] == 0 and w["compiled"] == w["executables"],
              f"cold store compiled everything: {w}")
        in_program = _kernel_in_decode(dense, kernel, "attn_impl='auto'")
        streams = _serve(dense, reqs, new_tokens, timeout)
        check(dense.status()["rung"] == cache_lengths[-1],
              f"a request grew the cache to rung {cache_lengths[-1]}")
        dense.shutdown()
        out["dense"] = {"warmup": w, "tpu_custom_call_in_decode": in_program}
        say(f"serve: dense-cache server compiled {w['compiled']} executables "
            f"in {w['seconds']:.1f} s; tpu_custom_call in decode executables "
            f"by rung: {in_program}; {len(reqs)} requests x {new_tokens} "
            f"tokens completed, no replay, no restart")

        # -- paged server --------------------------------------------------
        pool = slots * cache_lengths[-1] // page_size + 1
        paged = server(BertDecoder(cfg, params, page_size=page_size,
                                   pool_pages=pool), "paged")
        wp = paged.warmup()
        p_in_program = _kernel_in_decode(paged, kernel, "paged, 'auto'")
        p_streams = _serve(paged, reqs, new_tokens, timeout)
        paged.shutdown()
        check(all(p_streams[i] == streams[i] for i in greedy),
              "greedy streams of the dense-cache and paged servers identical")
        sampled_same = all(p_streams[i] == streams[i]
                           for i in range(len(reqs)) if i not in greedy)
        out["paged"] = {"warmup": wp, "pool_pages": pool,
                        "tpu_custom_call_in_decode": p_in_program,
                        "sampled_streams_equal_dense": sampled_same}
        say(f"serve: paged server ({pool} pages of {page_size}) compiled "
            f"{wp['compiled']} in {wp['seconds']:.1f} s; greedy streams "
            f"identical to dense-cache; sampled streams identical: "
            f"{sampled_same}")

        # -- warm restart from the first server's directory ----------------
        warm = server(BertDecoder(cfg, params), "dense")
        ww = warm.warmup()
        check(ww["compiled"] == 0 and ww["from_disk"] == ww["executables"],
              f"warm restart compiled nothing, loaded everything: {ww}")
        w_in_program = _kernel_in_decode(warm, kernel, "loaded from disk")
        w_streams = _serve(warm, reqs, new_tokens, timeout)
        warm.shutdown()
        check(all(w_streams[i] == streams[i] for i in greedy),
              "disk-loaded server answers with the same greedy streams")
        out["warm_restart"] = {"warmup": ww,
                               "tpu_custom_call_in_decode": w_in_program}
        say(f"serve: warm restart loaded {ww['from_disk']} executables from "
            f"disk in {ww['seconds']:.2f} s, compiled {ww['compiled']}, and "
            f"answered with the same greedy streams")

        # -- one greedy stream against the dense-attention reference -------
        short = min(greedy, key=lambda i: len(reqs[i][0]))
        need = len(reqs[short][0]) + new_tokens
        rung = next(c for c in cache_lengths if c >= need)
        bucket = next(b for b in prompt_buckets if b >= len(reqs[short][0]))
        ref = server(BertDecoder(cfg, params, attn_impl="dense"), "reference",
                     cache_lengths=[rung], prompt_buckets=[bucket])
        ref.warmup()
        _kernel_in_decode(ref, False, "attn_impl='dense'")
        r_stream = _serve(ref, [reqs[short]], new_tokens, timeout)[0]
        ref.shutdown()
        differ = next((i for i, (a, b) in enumerate(zip(streams[short],
                                                        r_stream)) if a != b),
                      None)
        out["kernel_vs_dense_reference"] = {"request": short,
                                            "first_difference": differ}
        say(f"serve: request {short} (greedy, prompt {len(reqs[short][0])}) "
            f"kernel vs dense-attention reference: "
            + ("identical" if differ is None else
               f"first difference at token {differ} of {new_tokens} "
               f"(reported, not fatal)"))
        return out
    finally:
        # serialized whole-model executables are tens of MiB each; the
        # output directory is for reports
        shutil.rmtree(exec_dir, ignore_errors=True)


# ------------------------------------------------------------- --chips 4 ---
def phase_dp(workers=4, batch=128, steps=5, hw=224, classes=1000, seed=0,
             rtol_first=1e-3, rtol=2e-2):
    """ParallelWrapper over `workers` chips beside plain fit() on one,
    same seed, same batches.

    Tolerances on the losses, relative. The FIRST loss is a forward pass
    over identical parameters and data: per example the bf16 arithmetic is
    the same on one chip and on four, and only the f32 batch-norm
    reductions are reordered across shards — `rtol_first` 1e-3 (on four
    v5e chips it came to 8.9e-8). Later losses compound that seed through
    the optimizer; on the chip the worst of five steps was 2.2e-3, so
    `rtol` is 2e-2, ten times that. A missing all-reduce, per-shard
    batch statistics or a wrong global batch move the first loss or the
    descent by far more. (A rehearsal at a toy size is much more chaotic
    — 5 % by the fifth step on virtual CPU devices — and passes looser
    values.)"""
    import jax
    from jax.sharding import NamedSharding

    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    devs = jax.devices()[:workers]
    check(len(set(devs)) == workers, f"{workers} distinct devices")

    net, clock = _train_resnet50(
        batch, steps, hw, classes, seed,
        trainer=lambda n: ParallelWrapper.Builder(n).workers(workers).build())
    leaves = jax.tree_util.tree_leaves(net._params)
    for leaf in leaves:
        sh = leaf.sharding
        check(isinstance(sh, NamedSharding) and "dp" in sh.mesh.axis_names,
              f"parameter carries a dp NamedSharding: {sh}")
        check(sh.device_set == set(devs),
              f"parameter spans the {workers} devices: {sh.device_set}")
        held = {s.device for s in leaf.addressable_shards
                if s.data.size == leaf.size}
        check(held == set(devs),
              f"every device holds a live replica: {held}")
    mem = {str(d): (d.memory_stats() or {}).get("bytes_in_use")
           for d in devs}
    check(all(v is None or v > 0 for v in mem.values()),
          f"every device has bytes in use: {mem}")
    dp_losses, dp_seconds, n_leaves = clock.losses, clock.seconds, len(leaves)
    del net, leaves, leaf

    _, solo_clock = _train_resnet50(batch, steps, hw, classes, seed)
    rel = [abs(a - b) / abs(b) for a, b in zip(dp_losses, solo_clock.losses)]
    check(rel[0] <= rtol_first and max(rel) <= rtol,
          f"dp and one-chip losses agree (first to {rtol_first}, all to "
          f"{rtol}): {rel}; dp {dp_losses} one chip {solo_clock.losses}")
    out = {"workers": workers, "dp_losses": dp_losses,
           "one_chip_losses": solo_clock.losses, "rel_diff": rel,
           "rtol_first": rtol_first, "rtol": rtol, "bytes_in_use": mem,
           "dp_first_step_s_incl_compile": round(dp_seconds[0], 2),
           "dp_later_step_s": [round(s, 4) for s in dp_seconds[1:]]}
    say(f"dp: ParallelWrapper workers={workers} global batch={batch}, "
        f"{n_leaves} parameter arrays "
        f"replicated under a dp NamedSharding on {[str(d) for d in devs]}, "
        f"bytes in use {mem}")
    say(f"dp: losses {[round(v, 4) for v in dp_losses]} vs one chip "
        f"{[round(v, 4) for v in solo_clock.losses]}: relative "
        f"differences {[float(f'{r:.2g}') for r in rel]} (tolerance "
        f"{rtol_first} on the first, {rtol} on all); first dp step "
        f"(compile + run) {out['dp_first_step_s_incl_compile']} s")
    return out


# ------------------------------------------------------------------ main ---
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel phase and what it "
                         "is compared with (needs four chips)")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()                      # the first act
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or device["count"] < args.chips:
        # no result on stdout: what was found goes to stderr
        print(json.dumps({"ok": False, "device": device,
                          "error": f"need {args.chips} TPU chip(s)"}),
              file=sys.stderr, flush=True)
        return 1
    t0 = time.perf_counter()
    say(f"device {device}; jax {jax.__version__}")

    from deeplearning4j_tpu.runtime import executables, native_lib
    from deeplearning4j_tpu.util.hostkey import enable_compile_cache

    cache = enable_compile_cache()
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    say(f"compile cache at {cache} ("
        + ("JAX_COMPILATION_CACHE_DIR" if from_env else "checkout default")
        + ")")
    executables.configure_persistent_cache()   # counts cache hits/misses
    native = native_lib.available()
    say(f"native_lib.available() = {native}")
    check(native, "the native runtime builds from dl4j_native.cpp and loads")

    os.makedirs(OUT_DIR, exist_ok=True)
    report = {"device": device, "jax": jax.__version__, "compile_cache": cache}
    if args.chips == 4:
        report["dp"] = phase_dp()
    else:
        report["train"] = phase_train()
        report["serve"] = phase_serve(OUT_DIR)
    report["persistent_compile_cache"] = executables.persistent_cache_stats()
    report["seconds"] = round(time.perf_counter() - t0, 1)
    say(f"persistent compile cache {report['persistent_compile_cache']}; "
        f"whole run {report['seconds']} s")
    with open(os.path.join(OUT_DIR, f"report_chips{args.chips}.json"),
              "w") as f:
        json.dump(report, f, indent=1, default=str)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
