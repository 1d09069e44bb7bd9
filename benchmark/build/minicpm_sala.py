"""The system under test for ``"architecture": "minicpm_sala"``:
``InferenceServer`` over ``Engine`` over the program's ``SparseLinearLLM``
(block-sparse attention layers whose selection reads pooled keys, lightning
linear-attention layers with per-slot float32 state, muP scalings), built
from the configuration file alone: no preset, no ``TDT_*`` variable. The
pool's page is the selection's block, so the server is given the
configuration's ``serving.block_size`` and the two are held equal. What
knows the program's model class and the published keys lives here."""

from __future__ import annotations

# The same fields hold the devices' memory: the parameters' pytree and the
# server's cache (the pool pair and the slots' state).
from benchmark.build.qwen3_dense import release  # noqa: F401

KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def model_config(cfg: dict):
    """The program's ``SparseLinearConfig`` for the configuration file."""
    from triton_dist_tpu.models import SparseLinearConfig

    sp = cfg["assumed"]["sparse_config"]
    if cfg["model_type"] != "minicpm_sala" or cfg["hidden_act"] != "silu":
        raise ValueError("the program runs minicpm_sala's block with silu")
    if cfg["attn_use_rope"] or not cfg["lightning_use_rope"] or not cfg["qk_norm"]:
        raise ValueError("sparse layers unrotated, lightning layers rotated, q and k normed")
    if not (cfg["use_output_gate"] and cfg["use_output_norm"] and cfg["attn_use_output_gate"]):
        raise ValueError("both mixers gate their output, the lightning one norms it first")
    if cfg["tie_word_embeddings"] or cfg["attention_bias"]:
        raise ValueError("the head is untied and nothing has a bias")
    if cfg["lightning_nkv"] != cfg["lightning_nh"] or cfg["lightning_scale"] != "1/sqrt(d)":
        raise ValueError("lightning layers: a K/V head a query head, scale 1/sqrt(d)")
    return SparseLinearConfig(
        vocab_size=int(cfg["vocab_size"]), hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        mixer_types=tuple(KINDS[m] for m in cfg["mixer_types"]),
        published_layers=int(cfg["published"]["num_hidden_layers"]),
        num_q_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]), head_dim=int(cfg["head_dim"]),
        lightning_heads=int(cfg["lightning_nh"]),
        lightning_head_dim=int(cfg["lightning_head_dim"]),
        kernel_size=int(sp["kernel_size"]), kernel_stride=int(sp["kernel_stride"]),
        block_size=int(sp["block_size"]), topk=int(sp["topk"]),
        init_blocks=int(sp["init_blocks"]), window_size=int(sp["window_size"]),
        scale_emb=float(cfg["scale_emb"]), scale_depth=float(cfg["scale_depth"]),
        dim_model_base=int(cfg["dim_model_base"]), rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]), max_len=int(cfg["serving"]["max_len"]),
        dtype=str(cfg["torch_dtype"]),
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
    mc = model_config(cfg)
    if mc.block_size != int(sv["block_size"]):
        raise ValueError(f"the selection's block is {mc.block_size}, the configuration's "
                         f"page {sv['block_size']}: a selected block must be a page")
    ctx = initialize_distributed(
        devices=list(devices), axis_names=(sv["mesh_axis"],), set_default=False)
    model = SparseLinearLLM(mc, ctx, key=jnp.asarray(key))
    engine = Engine(model, backend=sv["backend"], max_len=int(sv["max_len"]))
    server = InferenceServer(engine, num_slots=int(sv["slots"]), chunk=int(sv["chunk"]),
                             prefill_chunk=int(sv["prefill_chunk"]),
                             block_size=int(sv["block_size"]))
    jax.block_until_ready(model.params)
    return model, engine, server
