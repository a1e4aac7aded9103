"""Times the head's loss alone on the chip: ``value_and_grad`` of
``chunked_causal_lm_loss`` over hidden states and head kernel at the
benchmark cells' shapes, over chunk sizes. A diagnostic for the chunk rule
(``head_loss_chunk``), never a benchmark measurement.

    python3 scripts/head_loss_chunks.py [--root DIR] [--chunks 256,1024,None]

``--root`` takes the package from another checkout (a parent unpacked under
``.chip_archive/``), whose ``None`` is its own default.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

SHAPES = {  # (batch, sequence, hidden, vocabulary)
    "mistral-7b.steady-1g": (4, 4096, 4096, 32000),
    "mistral-7b.steady-4g": (1, 4096, 4096, 32000),
    "joyai-llm-flash": (1, 8192, 2048, 16160),
    "qwen3-next-80b-a3b": (1, 8192, 2048, 18992),
    "trinity-mini": (1, 8192, 2048, 25024),
    "internlm2-1.8b": (1, 1024, 2048, 92544),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=".")
    ap.add_argument("--chunks", default="None")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.transformer import chunked_causal_lm_loss

    dev = jax.devices()[0]
    for name in args.shapes.split(","):
        b, s, e, v = SHAPES[name]
        k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
        h = jax.random.normal(k1, (b, s, e), jnp.bfloat16)
        w = 0.02 * jax.random.normal(k2, (e, v), jnp.float32)
        t = jax.random.randint(k3, (b, s), 0, v)
        for c in args.chunks.split(","):
            kw = {} if c == "None" else {"chunk_size": int(c)}
            f = jax.jit(jax.value_and_grad(
                lambda h, w: chunked_causal_lm_loss(h, w, t, **kw),
                argnums=(0, 1)))
            try:
                out = jax.block_until_ready(f(h, w))
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    out = f(h, w)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t0) / args.reps * 1e3
            except Exception as err:  # noqa: BLE001: a size that does not fit
                print(json.dumps({"shape": name, "chunk": c,
                                  "error": str(err)[:200]}), flush=True)
                continue
            flops = 6 * b * s * e * v
            print(json.dumps({
                "root": args.root, "device": dev.device_kind, "shape": name,
                "chunk": c, "ms": round(ms, 3),
                "needed_tflops": round(flops / ms / 1e9, 1),
                "loss": float(out[0])}), flush=True)


if __name__ == "__main__":
    main()
