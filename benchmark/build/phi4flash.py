"""The system under test for ``"architecture": "phi4flash"``:
``InferenceServer`` over ``Engine`` over the program's ``HybridSSMLLM``
(Mamba layers with per-slot recurrent state, sliding-window rings, one full
attention layer whose K/V the cross-attention layers read, gated memory
units), built from the configuration file alone: no preset, no ``TDT_*``
variable. What knows the program's model class and the published keys lives
here."""

from __future__ import annotations

# The same fields hold the devices' memory: the parameters' pytree and the
# server's cache (the pool pair and the slots' state).
from benchmark.build.qwen3_dense import release  # noqa: F401


def model_config(cfg: dict):
    """The program's ``HybridSSMConfig`` for the configuration file."""
    from triton_dist_tpu.models import HybridSSMConfig

    a = cfg["assumed"]
    if cfg["model_type"] != "phi4flash" or cfg["hidden_act"] != "silu":
        raise ValueError("the program runs phi4flash's block with silu")
    if not cfg["tie_word_embeddings"] or cfg["mlp_bias"] or cfg["lm_head_bias"]:
        raise ValueError("the head is the embedding, and neither it nor the MLP has a bias")
    return HybridSSMConfig(
        vocab_size=int(cfg["vocab_size"]), hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_layers=int(cfg["num_hidden_layers"]), num_q_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        sliding_window=int(cfg["sliding_window"]), mb_per_layer=int(cfg["mb_per_layer"]),
        d_state=int(a["d_state"]), d_conv=int(a["d_conv"]), expand=int(a["expand"]),
        dt_rank=int(a["dt_rank"]), layer_norm_eps=float(cfg["layer_norm_eps"]),
        dtype=str(cfg["torch_dtype"]),
    )


def build(cfg: dict, key, devices):
    """(model, engine, server), the weights drawn on the devices from
    ``key`` (a legacy uint32[2] key) and there when this returns."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.models import Engine, HybridSSMLLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.serving import InferenceServer

    sv = cfg["serving"]
    ctx = initialize_distributed(
        devices=list(devices), axis_names=(sv["mesh_axis"],), set_default=False)
    model = HybridSSMLLM(model_config(cfg), ctx, key=jnp.asarray(key))
    engine = Engine(model, backend=sv["backend"], max_len=int(sv["max_len"]))
    server = InferenceServer(engine, num_slots=int(sv["slots"]), chunk=int(sv["chunk"]),
                             prefill_chunk=int(sv["prefill_chunk"]))
    if server.block_size != int(sv["block_size"]):
        raise ValueError(f"server block size {server.block_size}, configuration "
                         f"states {sv['block_size']}")
    jax.block_until_ready(model.params)
    return model, engine, server
