"""One admit of each long prompt bucket, alone, on the chip: a serving
cell's prefill program over one prompt in the bucket of 8192 and one in
that of 16384 tokens, its device time by scope.

    python3 scripts/admit_probe.py <out.json> [<config> [<lengths> ...]]

`<config>` names a file of `benchmarks/configs/` whose family serves
through `Lfm2MoeDecoder`, `AfmoeDecoder` or `MLADecoder` (default
`lfm2_24b_a2b_pp5`). Each `<lengths>` is a prompt length for each bucket,
`<at 8192>,<at 16384>` (default `8192,16384`, the buckets full); the
prompt's rows past its length are the padding a server sends (zeros), and
one program a bucket serves every length. The weights are the cell's own,
made on the device from one fixed seed, so two trees run from their roots
read the same admit: the wall clock of three calls, the device time of a
fourth under the profiler split by the program's scopes (a layer's scope
folded away), a digest of the logits, which says whether the two trees'
results are the same bits, and, where the decoder reckons them, the
prefill attention kernel's grid steps a head (`prefill_attn_pairs`).
Traces go to `benchmarks/.cache/admit_probe/`."""
import collections
import glob
import hashlib
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402
import numpy as np                                    # noqa: E402

from benchmarks.readers import program_span           # noqa: E402
from deeplearning4j_tpu.generation import decode      # noqa: E402

DECODERS = {"lfm2_moe_serve": "Lfm2MoeDecoder",
            "afmoe_serve": "AfmoeDecoder",
            "deepseek_v3_serve": "MLADecoder"}
SEED = 3000004530
SLOTS, RUNG = 48, 18432


def by_scope(path):
    """Device ms of the last whole prefill in a trace, by scope path with
    the layer folded away."""
    tr = program_span.load(path)
    runs = sorted((s, e) for name, s, e in tr["modules"]
                  if "lambda" in name)[-1:]
    out = collections.Counter()
    for (parts, own), t in program_span.by_scope(tr, runs).items():
        if parts and parts[0].startswith("layer"):
            parts = parts[1:]
        out["/".join(parts) or f"(none) {own}"] += 1e3 * t
    return (runs[0][1] - runs[0][0]) * 1e3, out


def main(out_path, config="lfm2_24b_a2b_pp5", *lengths):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config + ".json")) as f:
        file = json.load(f)
    family = importlib.import_module(f"benchmarks.families.{file['family']}")
    built = family.Built(file, SEED)
    dec = getattr(decode, DECODERS[file["family"]])(built.cfg, built.params)
    cache = jax.jit(lambda: dec.init_cache(SLOTS, RUNG))()
    admit = jax.jit(lambda p, c, slot, prompt, plen: dec.prefill(
        (p,), c, slot, prompt, plen), donate_argnums=(1,))
    rng = np.random.default_rng(7)
    buckets = (8192, 16384)
    tokens = {b: rng.integers(0, built.vocab, b) for b in buckets}
    plens = [tuple(map(int, n.split(","))) for n in lengths or ["8192,16384"]]
    result = {}
    for plen, bucket in ((n[i], b) for n in plens
                         for i, b in enumerate(buckets)):
        prompt = jnp.asarray(np.where(np.arange(bucket) < plen,
                                      tokens[bucket], 0), jnp.int32)
        args = (jnp.int32(1), prompt, jnp.int32(plen))
        name = f"{plen}_of_{bucket}"
        walls = []
        with jax.default_matmul_precision("default"):
            for i in range(5):
                start = time.perf_counter()
                if i == 4:
                    where = os.path.join(ROOT, "benchmarks", ".cache",
                                         "admit_probe", f"{config}_{name}")
                    with jax.profiler.trace(where):
                        cache, logits = admit(built.params, cache, *args)
                        jax.block_until_ready(logits)
                else:
                    cache, logits = admit(built.params, cache, *args)
                    jax.block_until_ready(logits)
                if 1 <= i <= 3:                   # the first compiles
                    walls.append(1e3 * (time.perf_counter() - start))
        device_ms, scopes = by_scope(sorted(glob.glob(os.path.join(
            where, "plugins", "profile", "*", "*.xplane.pb")))[-1])
        result[name] = {
            "wall_ms": [round(w, 3) for w in walls],
            "device_ms": round(device_ms, 3),
            "scopes": {k: round(v, 3) for k, v in scopes.most_common(16)},
            "logits_sha256": hashlib.sha256(
                np.asarray(logits).tobytes()).hexdigest()[:16]}
        if hasattr(dec, "prefill_attn_pairs"):
            result[name]["attn_pairs"] = dec.prefill_attn_pairs(plen, bucket)
        print(json.dumps({name: result[name]}), flush=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:])
