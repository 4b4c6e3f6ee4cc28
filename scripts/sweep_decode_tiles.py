"""The decode kernel alone under `lengths`, on the chip: the table behind
`decode_tile_rows` (kernels/flash_attention.py).

    chiprun -- python3 scripts/sweep_decode_tiles.py [--parent FILE]

Two shapes, each under positions drawn as its cell draws them (a slot
somewhere along its answer): BERT-base's `(64, 512, 768)` float32 and
bfloat16 leaves, 12 heads of 64, prompts log-uniform 16-64 and outputs
128-256; Keye's `(32, 18432, 512)` bfloat16 leaves, 32 query heads over 4 KV
heads of 128, prompts 4096-16384 and outputs 1024-2048 under a selection of
2048 rows. Every tile that divides the rung, the whole rung without
`lengths`, and the rule's own choice; each against the einsum masked softmax
at `highest` precision. `--parent` names another commit's
`kernels/flash_attention.py` to run beside this one (its call with
`lengths` and a `block_k`), so that two forms of the kernel are timed in one
process on one chip. A call's time is the device's: the median `flash_fwd`
operation in a profiler trace of three runs of a program that holds four
passes of a layer's worth of independent calls (the wall clock over 12 more
runs stands beside it; it reads 0.06-0.08 ms a call more than the device,
whatever the shape). The table goes to `chiprun_out/sweep_decode_tiles.json`
too."""
import argparse
import glob
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

SHAPES = {
    # name: slots, rung, query heads, KV heads, head width, dtype, layers,
    # prompt (lo, hi), output (lo, hi), rows a mask keeps, tiles
    "bert_f32": (64, 512, 12, 12, 64, jnp.float32, 12, (16, 64), (128, 256),
                 None, (128, 256, 512)),
    "bert_bf16": (64, 512, 12, 12, 64, jnp.bfloat16, 12, (16, 64),
                  (128, 256), None, (128, 256, 512)),
    "keye_bf16": (32, 18432, 32, 4, 128, jnp.bfloat16, 4, (4096, 16384),
                  (1024, 2048), 2048, (512, 1024, 2048)),
    # the rehearsal off the chip (interpret mode; its times mean nothing)
    "toy": (3, 512, 4, 4, 32, jnp.float32, 2, (16, 64), (128, 256), 64,
            (128, 512)),
}


PASSES = 4
ON_CHIP = jax.default_backend() == "tpu"


def timed(fn, *args, reps=12):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t)
    return float(np.median(ts))


def device_ms(fn, *args, runs=3):
    """The median device time of a `flash_fwd` operation over `runs` runs
    of `fn`, in ms, from a profiler trace (read as the benchmark's reader
    reads one)."""
    from benchmarks.readers import program_span
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(runs):
                jax.block_until_ready(fn(*args))
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        ops = program_span.load(path)["ops"]
    return 1e3 * float(np.median([end - start for op, start, end, _ in ops
                                  if op.lstrip("%").startswith("flash_fwd")]))


def sweep(name, forms, seed):
    (slots, rung, hq, hkv, d, dtype, layers, prompt, output, keep,
     tiles) = SHAPES[name]
    rng = np.random.default_rng(seed)
    p, o = (np.exp(rng.uniform(*np.log(span), slots))
            for span in (prompt, output))
    in_use = np.minimum(p + rng.uniform(0, 1, slots) * o + 1,
                        rung).astype(np.int32)
    rows = np.arange(rung)[None, :] < in_use[:, None]
    if keep:       # a selection of the rows in use, as an indexer leaves it
        score = np.where(rows, rng.random((slots, rung)), -1)
        rows &= score >= np.sort(score, axis=1)[:, -keep][:, None]
    mask, lengths = jnp.asarray(rows), jnp.asarray(in_use)
    keys = jax.random.split(jax.random.key(seed), 2 * layers + 1)
    ks, vs = ([jax.random.normal(k, (slots, rung, hkv * d), dtype)
               for k in keys[i:2 * layers:2]] for i in (0, 1))
    q = jax.random.normal(keys[-1], (slots, hq, d), dtype)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, k, v, m: fa._masked_attend(
            q[:, :, None], k, v, m[:, None, :])[:, :, 0])(
                q, ks[0], vs[0], mask).astype(jnp.float32)
    row_bytes = 2 * hkv * d * jnp.dtype(dtype).itemsize
    out = {"rows_in_use": int(in_use.sum()), "mean_rows_in_use":
           float(in_use.mean()),
           "rule": fa.decode_tile_rows(rung, hkv * d, dtype)}
    print(f"{name}: {slots} slots of {rung} rows, {in_use.mean():.1f} in "
          f"use a slot, the rule gives tiles of {out['rule']}")

    def case(label, call, read):
        # another query a pass, so that no two calls are one to XLA
        fn = jax.jit(lambda q, ks, vs, m, n: [
            call(q * (1 + r), k, v, m, n)
            for r in range(PASSES) for k, v in zip(ks, vs)])
        wall = 1e3 * timed(fn, q, ks, vs, mask, lengths) / (PASSES * layers)
        ms = device_ms(fn, q, ks, vs, mask, lengths) if ON_CHIP else wall
        # the first call of the first pass: q as it is, over ks[0], vs[0]
        gap = float(jnp.abs(fn(q, ks, vs, mask, lengths)[0].astype(
            jnp.float32) - want).max())
        out[label] = {"ms": ms, "wall_ms": wall, "rows_read": read,
                      "gap": gap}
        print(f"  {label}: {ms:.4f} ms a call (wall clock {wall:.4f}), "
              f"{read} rows read ({read * row_bytes / ms / 1e6:.1f} GB/s; "
              f"the rows in use at "
              f"{out['rows_in_use'] * row_bytes / ms / 1e6:.1f}), gap to "
              f"the dense softmax {gap:.5f}", flush=True)

    case("whole_rung_no_lengths", lambda q, k, v, m, n:
         fa.flash_attention_decode(q, k, v, m & (
             jnp.arange(rung)[None, :] < n[:, None]), impl="pallas"),
         slots * rung)
    for form, mod in forms.items():
        for tile in tiles:
            case(f"{form}_{tile}", lambda q, k, v, m, n, tile=tile, mod=mod:
                 mod.flash_attention_decode(q, k, v, m, impl="pallas",
                                            lengths=n, block_k=tile),
                 int((-(-in_use // tile) * tile).sum()))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="another commit's flash_attention.py")
    ap.add_argument("--shapes", nargs="*",
                    default=[name for name in SHAPES if name != "toy"])
    ap.add_argument("--seed", type=int, default=38)
    args = ap.parse_args()
    if not ON_CHIP and args.shapes != ["toy"]:
        sys.exit("the kernel is measured at the cells' shapes, on a TPU")
    forms = {"this": fa}
    if args.parent:
        spec = importlib.util.spec_from_file_location("fa_parent",
                                                      args.parent)
        forms["parent"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(forms["parent"])
    table = {name: sweep(name, forms, args.seed) for name in args.shapes}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/sweep_decode_tiles.json", "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
