"""The system under test for ``"architecture": "glm_moe_dsa"``:
``InferenceServer`` over ``Engine`` over the program's ``LatentSparseLLM``
(latent attention over a latent paged pool, the learned sparse selection,
the sigmoid-routed expert layer that is told which experts it holds), built
from the configuration file alone: no preset, no ``TDT_*`` variable. What
knows the program's model class and the published keys lives here."""

from __future__ import annotations

# The same fields hold the devices' memory: the parameters' pytree and the
# server's pool pair.
from benchmark.build.qwen3_dense import release  # noqa: F401


def model_config(cfg: dict):
    """The program's ``LatentSparseConfig`` for the configuration file."""
    from triton_dist_tpu.models import LatentSparseConfig

    kinds = {"dense": "dense", "sparse": "experts"}
    if int(cfg["n_group"]) != 1 or int(cfg["topk_group"]) != 1:
        raise ValueError("group-limited routing is not in the program")
    if cfg["scoring_func"] != "sigmoid" or not cfg["rope_interleave"]:
        raise ValueError("the program routes by sigmoid and turns interleaved pairs")
    if int(cfg["n_shared_experts"]) != 1 or int(cfg["num_nextn_predict_layers"]) != 0:
        raise ValueError("one shared expert, and no next-token module is served")
    first, count = cfg["experts_held"]
    if count != int(cfg["n_routed_experts"]):
        raise ValueError("n_routed_experts counts the experts held here")
    return LatentSparseConfig(
        vocab_size=int(cfg["vocab_size"]), hidden_size=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]), q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]), qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]), v_head_dim=int(cfg["v_head_dim"]),
        index_n_heads=int(cfg["index_n_heads"]), index_head_dim=int(cfg["index_head_dim"]),
        index_rope_dim=int(cfg["assumed"]["index_rope_dim"]), index_topk=int(cfg["index_topk"]),
        index_norm_eps=float(cfg["assumed"]["index_norm_eps"]),
        mlp_kinds=tuple(kinds[k] for k in cfg["mlp_layer_types"]),
        index_kinds=tuple(cfg["indexer_types"]),
        intermediate_size=int(cfg["intermediate_size"]),
        expert_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_experts=int(cfg["published"]["n_routed_experts"]),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        experts_held=(int(first), int(count)),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
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
