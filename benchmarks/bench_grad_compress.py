"""Beyond-paper: clustered gradient compression for the cross-pod exchange.

Reports payload reduction and the training-quality delta over a short run
of the reduced LM (with and without 16-level clustered quantization +
error feedback).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ShapeConfig, get_config
from repro.data.synthetic import token_stream
from repro.models.registry import build_model
from repro.optim import AdamW
from repro.core import ClusterSpec, MergeSpec
from repro.train.compress import compressed_bytes, make_grad_compressor


def run(csv):
    cfg = get_config("llama3-8b").reduced()
    model = build_model(cfg)
    shape = ShapeConfig("t", 32, 8, "train")
    params = model.init(jax.random.PRNGKey(0))
    raw, small = compressed_bytes(params, 16)
    csv("grad_compress/payload", 0.0,
        f"fp32={raw / 1e6:.1f}MB;4bit+codebook={small / 1e6:.2f}MB;"
        f"reduction={raw / small:.1f}x")

    def loss_fn(p, batch):
        ctx = model.make_ctx(jnp.arange(shape.seq_len), q_chunk=32)
        return model.loss(p, batch, ctx, remat=False)

    losses = {}
    for mode in ("baseline", "compressed"):
        opt = AdamW(lr=3e-3)
        p = model.init(jax.random.PRNGKey(0))
        st = opt.init(p)
        # the codebook fit declared as a spec: 16 levels, landmark init
        comp = make_grad_compressor(spec=ClusterSpec(
            merge=MergeSpec(k=16, iters=8, init="landmark")))
        resid = None
        grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        hist = []
        for step in range(20):
            batch = {k: jnp.asarray(v) for k, v in token_stream(
                step, shape.global_batch, shape.seq_len, cfg.vocab).items()}
            val, g = grad_fn(p, batch)
            if mode == "compressed":
                g, resid = comp(g, resid)
            p, st, _ = opt.update(g, st, p)
            hist.append(float(val))
        losses[mode] = hist
        csv(f"grad_compress/loss_{mode}", 0.0,
            f"start={hist[0]:.3f};end={hist[-1]:.3f}")
    delta = losses["compressed"][-1] - losses["baseline"][-1]
    csv("grad_compress/quality_delta", 0.0, f"end_loss_delta={delta:+.4f}")
    return losses


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
