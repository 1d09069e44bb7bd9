"""The system under test for ``"architecture": "granite_hybrid"``:
``InferenceServer`` over ``Engine`` over the program's ``SparseLinearLLM``
with Mamba-2 (``"mamba2"``) and full attention (``"full"``) mixers, in
Granite's muP form (its three multipliers as stated numbers, the head tied
to the embedding), built from the configuration file alone: no preset, no
``TDT_*`` variable. What knows the program's model class and the published
keys lives here."""

from __future__ import annotations

# The same fields hold the devices' memory: the parameters' pytree and the
# server's cache (the pool pair and the slots' state).
from benchmark.build.qwen3_dense import release  # noqa: F401

KINDS = {"mamba": "mamba2", "attention": "full"}


def model_config(cfg: dict):
    """The program's ``SparseLinearConfig`` for the configuration file."""
    from triton_dist_tpu.models import SparseLinearConfig

    if cfg["model_type"] != "granitemoehybrid" or int(cfg["num_local_experts"]) != 0:
        raise ValueError("the program runs granitemoehybrid's dense block")
    if cfg["hidden_act"] != "silu" or cfg["normalization_function"] != "rmsnorm":
        raise ValueError("SwiGLU and RMSNorm")
    if cfg["position_embedding_type"] != "nope" or cfg["attention_bias"]:
        raise ValueError("attention without rotation and without bias")
    if int(cfg["mamba_n_groups"]) != 1 or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"]:
        raise ValueError("Mamba-2 with one group of B and C, a conv bias, no projection bias")
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the head is the embedding")
    d, heads = int(cfg["hidden_size"]), int(cfg["mamba_n_heads"])
    head = int(cfg["mamba_d_head"])
    if int(cfg["mamba_expand"]) * d != heads * head:
        raise ValueError("mamba_expand x hidden_size must be mamba_n_heads x mamba_d_head")
    return SparseLinearConfig(
        vocab_size=int(cfg["vocab_size"]), hidden_size=d,
        intermediate_size=int(cfg["shared_intermediate_size"]),
        mixer_types=tuple(KINDS[m] for m in cfg["layer_types"]),
        num_q_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=d // int(cfg["num_attention_heads"]),
        mamba_heads=heads, mamba_head_dim=head, mamba_state=int(cfg["mamba_d_state"]),
        mamba_conv=int(cfg["mamba_d_conv"]), mamba_chunk=int(cfg["mamba_chunk_size"]),
        scale_emb=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logit_multiplier=1.0 / float(cfg["logits_scaling"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        tie_embeddings=True, embed_std=float(cfg["embedding_init_std"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        max_len=int(cfg["serving"]["max_len"]), dtype=str(cfg["torch_dtype"]),
    )


def build(cfg: dict, key, devices):
    """(model, engine, server), the weights drawn on the devices from
    ``key`` (a legacy uint32[2] key) and there when this returns."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.models import Engine, SparseLinearLLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.serving import InferenceServer

    sv = cfg["serving"]
    ctx = initialize_distributed(
        devices=list(devices), axis_names=(sv["mesh_axis"],), set_default=False)
    model = SparseLinearLLM(model_config(cfg), ctx, key=jnp.asarray(key))
    engine = Engine(model, backend=sv["backend"], max_len=int(sv["max_len"]))
    server = InferenceServer(engine, num_slots=int(sv["slots"]), chunk=int(sv["chunk"]),
                             prefill_chunk=int(sv["prefill_chunk"]),
                             block_size=int(sv["block_size"]))
    jax.block_until_ready(model.params)
    return model, engine, server
