"""The system under test for ``"architecture": "qwen3_moe"``, the toy's
second architecture: ``InferenceServer`` over ``Engine`` over the program's
expert-parallel ``EPMoELLM``. Nothing of it is under ``benchmark/``: the
harness finds this file by the configuration's ``architecture``."""

import dataclasses

# ``EPMoELLM`` is a ``DenseLLM``: the same fields hold the devices' memory.
from benchmark.build.qwen3_dense import release  # noqa: F401


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration file: the named
    preset at the file's depth, refused unless every width agrees."""
    from triton_dist_tpu.models import PRESETS

    preset = PRESETS[cfg["serving"]["preset"]]
    mc = dataclasses.replace(preset, num_layers=int(cfg["num_hidden_layers"]))
    same = {
        "hidden_size": mc.hidden_size, "moe_intermediate_size": mc.moe_intermediate_size,
        "num_experts": mc.num_experts, "num_experts_per_tok": mc.top_k,
        "norm_topk_prob": mc.norm_topk_prob,
        "num_attention_heads": mc.num_q_heads, "num_key_value_heads": mc.num_kv_heads,
        "head_dim": mc.head_dim, "vocab_size": mc.vocab_size,
        "rope_theta": mc.rope_theta, "rms_norm_eps": mc.rms_eps,
        "torch_dtype": mc.dtype, "tie_word_embeddings": mc.tie_word_embeddings,
    }
    wrong = {k: (cfg[k], v) for k, v in same.items() if cfg[k] != v}
    if wrong:
        raise ValueError(f"configuration file and preset disagree: {wrong}")
    return mc


def build(cfg: dict, key, devices):
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.models import Engine, EPMoELLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.serving import InferenceServer

    sv = cfg["serving"]
    ctx = initialize_distributed(
        devices=list(devices), axis_names=(sv["mesh_axis"],), set_default=False)
    model = EPMoELLM(model_config(cfg), ctx, key=jnp.asarray(key))
    engine = Engine(model, backend=sv["backend"], max_len=int(sv["max_len"]))
    server = InferenceServer(engine, num_slots=int(sv["slots"]), chunk=int(sv["chunk"]))
    if server.block_size != int(sv["block_size"]):
        raise ValueError(f"server block size {server.block_size}, configuration "
                         f"states {sv['block_size']}")
    jax.block_until_ready(model.params)
    return model, engine, server
