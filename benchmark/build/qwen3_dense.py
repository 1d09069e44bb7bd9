"""The system under test for ``"architecture": "qwen3_dense"``:
``InferenceServer`` over ``Engine`` over ``DenseLLM``, built as
``chip_smoke.py`` builds them, at the program's defaults (no ``TDT_*``
variable is set here). What knows the program's model class and the dense
block's keys of the configuration file lives here, not in the harness."""

from __future__ import annotations

import dataclasses


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration file: the named
    preset at the file's depth, refused unless every width agrees."""
    from triton_dist_tpu.models import PRESETS

    preset = PRESETS[cfg["serving"]["preset"]]
    mc = dataclasses.replace(preset, num_layers=int(cfg["num_hidden_layers"]))
    same = {
        "hidden_size": mc.hidden_size, "intermediate_size": mc.intermediate_size,
        "num_attention_heads": mc.num_q_heads, "num_key_value_heads": mc.num_kv_heads,
        "head_dim": mc.head_dim, "vocab_size": mc.vocab_size,
        "rope_theta": mc.rope_theta, "rms_norm_eps": mc.rms_eps,
        "torch_dtype": mc.dtype, "tie_word_embeddings": mc.tie_word_embeddings,
    }
    wrong = {k: (cfg[k], v) for k, v in same.items() if cfg[k] != v}
    if wrong or mc.is_moe:
        raise ValueError(f"configuration file and preset disagree: {wrong}")
    return mc


def build(cfg: dict, key, devices):
    """(model, engine, server), the weights drawn on the devices from
    ``key`` (a legacy uint32[2] key) and there when this returns."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.models import DenseLLM, Engine
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.serving import InferenceServer

    sv = cfg["serving"]
    ctx = initialize_distributed(
        devices=list(devices), axis_names=(sv["mesh_axis"],), set_default=False)
    model = DenseLLM(model_config(cfg), ctx, key=jnp.asarray(key))
    engine = Engine(model, backend=sv["backend"], max_len=int(sv["max_len"]))
    server = InferenceServer(engine, num_slots=int(sv["slots"]), chunk=int(sv["chunk"]))
    if server.block_size != int(sv["block_size"]):
        raise ValueError(f"server block size {server.block_size}, configuration "
                         f"states {sv['block_size']}")
    jax.block_until_ready(model.params)
    return model, engine, server


def release(model, engine, server) -> None:
    """Free what the program holds on the devices."""
    import jax

    server.shutdown(drain=False)
    held = [model.params, server.cache, getattr(engine, "_decode_extra", None)]
    for leaf in jax.tree.leaves(held):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
    server.cache = None
    model.params = None
