"""The system under test for ``"architecture": "axk1"``: ``InferenceServer``
over ``Engine`` over the program's ``LatentSparseLLM`` with no indexer on any
layer (latent attention over the whole cache: the causal flash kernel in
prefill, the absorbed kernel over the latent pool in decode), YaRN's rotary
table, a router without a bias, and the expert layer told which experts it
holds; built from the configuration file alone: no preset, no ``TDT_*``
variable. What knows the program's model class and the published keys lives
here."""

from __future__ import annotations

# The same fields hold the devices' memory: the parameters' pytree and the
# server's pool pair.
from benchmark.build.qwen3_dense import release  # noqa: F401

#: ``topk_method`` -> whether the router ranks by score plus a bias. "none":
#: the plain top-k of the scores (``assumed.topk_method``).
ROUTING = {"none": False}


def model_config(cfg: dict):
    """The program's ``LatentSparseConfig`` for the configuration file."""
    from triton_dist_tpu.layers.latent_sparse import Yarn
    from triton_dist_tpu.models import LatentSparseConfig

    if cfg["topk_method"] not in ROUTING:
        raise ValueError(f"topk_method {cfg['topk_method']!r}: the program routes by "
                         f"{sorted(ROUTING)} here (group-limited routing is not in it)")
    if cfg["scoring_func"] != "sigmoid" or int(cfg["n_shared_experts"]) != 1:
        raise ValueError("the program routes by sigmoid beside one shared expert")
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] or int(cfg["moe_layer_freq"]) != 1:
        raise ValueError("the head is untied, nothing has a bias, every later layer has experts")
    rs = cfg["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError("the rotary table is YaRN's")
    first, count = cfg["experts_held"]
    if count != int(cfg["n_routed_experts"]):
        raise ValueError("n_routed_experts counts the experts held here")
    layers, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    return LatentSparseConfig(
        vocab_size=int(cfg["vocab_size"]), hidden_size=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]), q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]), qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]), v_head_dim=int(cfg["v_head_dim"]),
        mlp_kinds=("dense",) * dense + ("experts",) * (layers - dense),
        index_kinds=("none",) * layers,
        intermediate_size=int(cfg["intermediate_size"]),
        expert_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_experts=int(cfg["published"]["n_routed_experts"]),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        experts_held=(int(first), int(count)),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        router_bias=ROUTING[cfg["topk_method"]],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=Yarn(
            factor=float(rs["factor"]), original_max=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"])),
        rms_eps=float(cfg["rms_norm_eps"]), dtype=str(cfg["torch_dtype"]),
    )


def build(cfg: dict, key, devices):
    """(model, engine, server), the weights drawn on the devices from
    ``key`` (a legacy uint32[2] key) and there when this returns."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.models import Engine, LatentSparseLLM
    from triton_dist_tpu.runtime.mesh import initialize_distributed
    from triton_dist_tpu.serving import InferenceServer

    sv = cfg["serving"]
    ctx = initialize_distributed(
        devices=list(devices), axis_names=(sv["mesh_axis"],), set_default=False)
    model = LatentSparseLLM(model_config(cfg), ctx, key=jnp.asarray(key))
    engine = Engine(model, backend=sv["backend"], max_len=int(sv["max_len"]))
    server = InferenceServer(engine, num_slots=int(sv["slots"]), chunk=int(sv["chunk"]),
                             prefill_chunk=int(sv["prefill_chunk"]))
    if server.block_size != int(sv["block_size"]):
        raise ValueError(f"server block size {server.block_size}, configuration "
                         f"states {sv['block_size']}")
    jax.block_until_ready(model.params)
    return model, engine, server
