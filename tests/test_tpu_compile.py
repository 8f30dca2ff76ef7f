"""Compiles for a described TPU v5e at published widths, with no chip
attached: the Pallas kernels of the model and serving paths, and the
OPT-1.3b hydra actor step at ``chip_smoke.py``'s training shape against one
chip's HBM. The TPU compiler refuses here what interpret mode cannot show:
blocks that break the (8, 128) tiling, kernels over the fast-memory limit,
programs that do not fit the device.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file."""
import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

_ROOT = pathlib.Path(__file__).resolve().parent.parent
# one v5e's HBM as the TPU runtime reports it usable (bytes_limit)
V5E_USABLE_BYTES = int(15.75 * 2 ** 30)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:                 # no TPU compiler here
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def on_chip(topo):
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return sds


def _compile_kernel(fn, *args):
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    return compiled


# OPT-1.3b attention: 32 heads of 64
H, D = 32, 64


def test_flash_attention_compiles(on_chip):
    from repro.kernels.flash_attention import flash_attention_fwd
    q = on_chip((4, 256, H, D))
    _compile_kernel(flash_attention_fwd, q, q, q)


def test_decode_attention_compiles(on_chip):
    from repro.kernels.decode_attention import decode_attention
    B, C = 4, 1024
    _compile_kernel(decode_attention, on_chip((B, H, D)),
                    on_chip((B, C, H, D)), on_chip((B, C, H, D)),
                    on_chip((B, C), jnp.int32), on_chip((B,), jnp.int32))


def test_paged_decode_attention_compiles(on_chip):
    from repro.paged.attention import paged_decode_attention
    B, pages, page, blocks = 8, 144, 16, 18
    _compile_kernel(paged_decode_attention, on_chip((B, H, D)),
                    on_chip((pages, page, H, D)),
                    on_chip((pages, page, H, D)),
                    on_chip((B, blocks), jnp.int32),
                    on_chip((B,), jnp.int32))


def test_rmsnorm_compiles(on_chip):
    from repro.kernels.rmsnorm import rmsnorm
    _compile_kernel(rmsnorm, on_chip((1024, 2048)), on_chip((2048,)))


def test_ssd_scan_compiles(on_chip):
    from repro.configs import get_config
    from repro.kernels.ssd_scan import ssd_scan
    cfg = get_config("mamba2_370m")
    ssm = cfg.ssm
    B, S = 1, 4 * ssm.chunk_size
    Hs, N = ssm.n_heads(cfg.d_model), ssm.d_state
    _compile_kernel(functools.partial(ssd_scan, chunk=ssm.chunk_size),
                    on_chip((B, S, Hs, ssm.head_dim)),
                    on_chip((B, S, Hs), jnp.float32),
                    on_chip((B, S, N)), on_chip((B, S, N)))


def test_hydra_actor_step_fits_one_chip(on_chip):
    """The PPO actor step of ``chip_smoke.py``'s phase A (OPT-1.3b hydra,
    rank-128 adapters, remat off as the paper configs set it): its
    arguments plus temporaries must fit one v5e."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", _ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from repro.configs import get_config
    from repro.models import Model
    from repro.steps import init_lora_train_state, make_lora_train_step

    cfg = get_config("opt_1_3b")
    model = Model(cfg)
    step = make_lora_train_step(model, cfg, kind="ppo")
    key = jax.random.PRNGKey(0)
    base = jax.eval_shape(model.init, key)
    state = jax.eval_shape(
        lambda k: init_lora_train_state(model.init_adapter(
            k, model.init(k), smoke.LORA_RANK), step.optimizer), key)
    place = lambda tree: jax.tree.map(
        lambda x: on_chip(x.shape, x.dtype), tree)
    S = smoke.PROMPT_LEN + smoke.GEN_LEN
    batch = {k: on_chip((smoke.TRAIN_BATCH, S), jnp.float32)
             for k in ("loss_mask", "advantages", "old_logp", "ref_logp",
                       "returns")}
    batch["tokens"] = on_chip((smoke.TRAIN_BATCH, S), jnp.int32)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        place(state), place(base), batch).compile()
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < V5E_USABLE_BYTES, (mem.argument_size_in_bytes,
                                     mem.temp_size_in_bytes)
