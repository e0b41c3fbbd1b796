"""Weight initialisation: the same seed gives the same weights in every
process, and an untied unembedding starts near the uniform prediction."""
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_DIGEST = """
import hashlib, jax, numpy as np
from repro.configs.registry import get_arch
from repro.models.model import Model
from repro.models import params as pdefs
p = pdefs.init_params(Model(get_arch("xlstm-350m").smoke).defs(),
                      jax.random.PRNGKey(7))
h = hashlib.sha256()
for leaf in jax.tree.leaves(p):
    h.update(np.asarray(leaf).tobytes())
print(h.hexdigest())
"""


def _digest(hashseed: str) -> str:
    env = dict(os.environ, PYTHONHASHSEED=hashseed, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", _DIGEST], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_init_identical_across_hash_seeds():
    """Per-leaf keys come from a CRC-32 of the leaf path, not Python's
    per-process salted ``hash``: two processes with different
    PYTHONHASHSEED draw the same weights."""
    assert _digest("1") == _digest("2")


def test_initial_loss_near_log_vocab():
    """xlstm-350m's smoke config (untied unembedding) starts within one nat
    of ln(vocab), the loss of a uniform prediction."""
    from repro.configs.registry import get_arch
    from repro.models.model import Model
    from repro.sharding.rules import ParallelContext
    cfg = get_arch("xlstm-350m").smoke
    assert not cfg.tie_embeddings
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                              cfg.vocab_size)
    loss, _ = model.loss(params, {"tokens": toks,
                                  "labels": jnp.roll(toks, -1, axis=1)},
                         ParallelContext())
    assert abs(float(loss) - math.log(cfg.vocab_size)) < 1.0, float(loss)
