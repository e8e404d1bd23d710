"""The paper's technique as a training-framework feature: guaranteed-error
approximate evaluation (see src/repro/aqpeval/).

    PYTHONPATH=src python examples/approx_eval.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.aqpeval import GuaranteedEvaluator
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import build_model


def main():
    cfg = get_config("internlm2-1.8b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_blocks, bsz, seq = 96, 2, 32
    rng = np.random.default_rng(1)
    shards = rng.integers(0, cfg.vocab_size, (n_blocks, bsz, seq + 1))

    @jax.jit
    def shard_loss(tokens):
        logits, _ = model.forward(params, {"tokens": tokens[:, :-1]})
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1).sum()

    calls = {"n": 0}

    def block_metric(ids):
        calls["n"] += len(ids)
        sums = np.array([float(shard_loss(jnp.asarray(shards[i]))) for i in ids])
        return sums, np.full(len(ids), bsz * seq, float)

    ev = GuaranteedEvaluator(n_blocks, block_metric, seed=3)
    res = ev.evaluate(error=0.05, confidence=0.9, pilot_blocks=16)
    s, c = block_metric(np.arange(n_blocks))
    truth = s.sum() / c.sum()
    print(f"approx eval loss : {res.estimate:.4f}  (<=5% error w.p. 90%)")
    print(f"exact eval loss  : {truth:.4f}  (achieved {abs(res.estimate-truth)/truth:.2%})")
    print(f"model calls      : {res.pilot_blocks + res.final_blocks}/{res.total_blocks} "
          f"shards ({res.blocks_saved_frac:.0%} of eval compute saved)")


if __name__ == "__main__":
    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    main()
