"""``bench/flops.py`` against hand counts for the paper's two actor models
at their published widths, and against the program's own parameter tree."""
import json

import jax
import pytest

from bench import flops
from bench.common import BENCH

OPT_1_3B = {"num_layers": 24, "d_model": 2048, "num_heads": 32,
            "num_kv_heads": 32, "d_ff": 8192, "vocab_size": 50272,
            "qkv_bias": True, "mlp_gated": False, "tie_embeddings": True}


def gpt2_xl():
    with open(BENCH / "configs" / "gpt2-xl-serve.json") as f:
        return json.load(f)["model"]


def test_parameter_counts_by_hand():
    # per layer: q,k,v,o 4 d^2 + q,k,v biases 3 d + MLP 2 d d_ff + 2 norms
    # 2 d; then the tied embedding V d and the final norm d
    assert flops.param_count(OPT_1_3B) == \
        24 * (4 * 2048**2 + 3 * 2048 + 2 * 2048 * 8192 + 2 * 2048) \
        + 50272 * 2048 + 2048 == 1_311_164_416
    assert flops.param_count(gpt2_xl()) == \
        48 * (4 * 1600**2 + 3 * 1600 + 2 * 1600 * 6400 + 2 * 1600) \
        + 50257 * 1600 + 1600 == 1_555_356_800


@pytest.mark.parametrize("dims", [OPT_1_3B, None], ids=["opt-1.3b",
                                                         "gpt2-xl"])
def test_parameter_count_matches_program_tree(dims):
    from repro.configs.base import ModelConfig
    from repro.models import Model
    m = dims or gpt2_xl()
    cfg = ModelConfig(name="x", family="dense", **m)
    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert flops.param_count(m) == n


def test_kv_bytes_per_token():
    assert flops.kv_bytes_per_token(gpt2_xl()) == 2 * 48 * 1600 * 2 \
        == 307_200
    assert flops.kv_bytes_per_token(OPT_1_3B) == 2 * 24 * 2048 * 2


def test_token_and_prefill_flops_by_hand():
    m = gpt2_xl()
    d, L, V = 1600, 48, 50257
    matmul = 4 * d * d + 2 * d * 6400
    # a token at context 10: 2 per multiply-add over the matrices, QK^T and
    # AV over 10 keys in every layer, and the logits
    assert flops.token_flops(m, 10) == \
        L * (2 * matmul + 4 * d * 10) + 2 * d * V
    # a 3-token prompt: contexts 1, 2, 3, logits at the last position only
    assert flops.prefill_flops(m, 3) == \
        3 * L * 2 * matmul + L * 4 * d * (1 + 2 + 3) + 2 * d * V


def test_decode_step_bytes_and_flops():
    m = gpt2_xl()
    contexts = [100, 300]
    assert flops.decode_bytes(m, contexts) == \
        2 * 1_555_356_800 + 307_200 * 400
    assert flops.decode_flops(m, contexts) == \
        flops.token_flops(m, 100) + flops.token_flops(m, 300)
