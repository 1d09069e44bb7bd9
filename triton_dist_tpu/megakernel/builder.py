"""ModelBuilder: assemble a decode step from fused task groups.

Reference: ``mega_triton_kernel/models/model_builder.py:86,216-336`` —
``make_*`` calls record the model's ops into the graph; ``build`` generates
the persistent kernel. TPU: ``make_*`` records tasks; ``build_layer_fn``
**consumes the scheduler's fusion groups** to pick kernels — an
``attn_front`` group lowers to ``fused_ln_qkv_rope``, an ``mlp_block`` group
to ``fused_mlp_block``, and any unmatched task to its standalone op — so a
mutated graph observably changes the generated kernel sequence (the
load-bearing analog of the reference's codegen dispatching on task_type,
``core/code_generator.py:158-166``). The chosen lowering is recorded in
``ModelBuilder.plan``.

Serving shape (``build_step_fn``): the whole model's decode step is ONE
graph — every layer's tasks recorded with ``@<layer>``-suffixed names, the
scoreboard policy emitting groups in dependency order so a layer's off-path
HBM cache scatter defers behind the next layer's attn-front. Per-slot
active masks and paged block tables enter as DATA operands (``input:active``
/ ``input:tables``), so one compiled step program serves every batch
composition — the Orca-style iteration-level masking and the
vLLM/PagedAttention table walk, inside mega tasks.

Knobs: ``TDT_MEGA_POLICY`` picks the schedule policy when the caller
doesn't (``scoreboard`` default; ``static`` / ``cost`` as in
``TaskGraph.schedule``).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from triton_dist_tpu.megakernel.graph import Task, TaskGraph
from triton_dist_tpu.megakernel.kernels import (
    _rmsnorm_rows,
    fused_attn_back,
    fused_ln_qkv_rope,
    fused_mlp_block,
    fused_moe_block,
    fused_paged_attn_back,
)


def default_schedule_policy() -> str:
    """Schedule policy when the caller doesn't pick one: ``TDT_MEGA_POLICY``
    env override, else ``scoreboard`` (the serving decode default)."""
    return os.environ.get("TDT_MEGA_POLICY", "scoreboard")


class ModelBuilder:
    """Records a transformer decode step's tasks and lowers them.

    Usage (mirrors the reference's builder):
        mb = ModelBuilder(config, axis="tp")
        layer_fn = mb.build_layer_fn()       # also populates mb.graph
        print(mb.graph.summary())            # audit the fusion schedule
        print(mb.plan)                       # kernels the schedule chose

    To audit/override the fusion, record first, mutate ``mb.graph``, then
    call ``build_layer_fn()`` — it lowers whatever the graph holds.

    ``paged=True`` switches the cache tasks to the block-pool layout
    (tables + active mask as data operands); ``moe_impl`` replaces the
    ``moe`` task's lowering with a caller-supplied ``(lp, x) -> y`` — the
    EP MoE model routes its AUTO-resolved a2a path through it.
    """

    def __init__(self, config, axis: str = "tp", world: int = 1,
                 mesh_axes=None, schedule_policy: str | None = None,
                 batch_hint: int = 8, ctx_hint: int = 4096,
                 paged: bool = False, moe_impl=None):
        self.config = config
        self.axis = axis
        self.world = world
        self.mesh_axes = mesh_axes
        self.schedule_policy = (schedule_policy if schedule_policy is not None
                                else default_schedule_policy())
        self.batch_hint = batch_hint
        self.ctx_hint = ctx_hint
        self._paged = paged
        self.moe_impl = moe_impl
        self.graph = TaskGraph()
        self.plan: list[str] = []

    # ------------------------------------------------------------ cost model
    def group_cost(self, gname: str, window) -> float:
        """Modeled fraction of the group's HBM traffic that fusing saves
        (intermediates stay in VMEM: each skips one write + one read). The
        "cost" schedule policy fuses only when this clears
        ``graph.COST_FUSE_THRESHOLD`` — the TPU-native remainder of the
        reference's scheduler-policy choice (``core/scheduler.py:103-157``):
        the schedule itself is static under XLA, so the load-bearing knob
        is which chains become custom kernels at the (batch, ctx) the
        builder is told to expect (``batch_hint``/``ctx_hint``)."""
        c = self.config
        b = self.batch_hint
        d = c.hidden_size
        hq = c.num_q_heads // self.world
        hkv = c.num_kv_heads // self.world
        hd = c.head_dim
        cols = (hq + 2 * hkv) * hd
        # Element counts, not bytes: every tensor in a group shares the
        # model dtype, so the itemsize cancels out of the ratio.
        if gname == "attn_front":
            saved = 2 * (b * d + 2 * b * cols)
            base = d * cols + b * d
        elif gname in ("attn_back", "attn_sweep"):
            saved = 2 * b * hq * hd  # attention output round-trip
            base = hq * hd * d + 2 * hkv * self.ctx_hint * hd * b
        elif gname == "mlp_block":
            ff = c.intermediate_size // self.world
            saved = 2 * (b * d + 3 * b * ff)
            base = 3 * d * ff + b * d
        elif gname == "moe_block":
            from triton_dist_tpu.kernels.moe_utils import capacity_for
            from triton_dist_tpu.layers.tp import MOE_CAPACITY_FACTOR

            ff = c.moe_intermediate_size // self.world
            e = c.num_experts
            cap = capacity_for(b, c.top_k, e, MOE_CAPACITY_FACTOR)
            saved = 2 * e * cap * ff
            base = 3 * e * d * ff + e * cap * d
        else:
            return 1.0  # unknown group: trust the static decision
        return saved / max(base, 1)

    # ------------------------------------------------------------- recording
    # All make_* accept a ``tag`` (task/value name suffix, "@<layer>" in the
    # step graph) and the wiring values that differ per layer; the defaults
    # reproduce the classic single-layer graph byte-for-byte.
    def make_attn_front(self, *, tag: str = "", x_in: str = "input:x"):
        g = self.graph
        g.add(Task(f"ln1{tag}", "rmsnorm", (x_in, "param:ln1"), (f"v:xn1{tag}",)))
        g.add(Task(f"qkv_proj{tag}", "linear", (f"v:xn1{tag}", "param:wqkv"), (f"v:qkv{tag}",)))
        g.add(Task(f"qk_norm{tag}", "head_norm", (f"v:qkv{tag}", "param:q_norm", "param:k_norm"), (f"v:qkv_n{tag}",)))
        g.add(Task(f"rope{tag}", "rope", (f"v:qkv_n{tag}", "input:pos"), (f"v:q{tag}", f"v:k{tag}", f"v:v{tag}")))

    def make_attn_back(self, *, tag: str = "", x_in: str = "input:x",
                       kc_in: str = "input:kc", vc_in: str = "input:vc",
                       split_sweep: bool = False):
        """Attention back-leg. Three recorded shapes:

        * classic (default): ``cache_update → flash_decode → o-proj-AR →
          residual`` — the 4-chain ``attn_back`` group.
        * ``split_sweep=True`` (contiguous step graph): the sweep
          (``flash_decode_append``, in-VMEM splice of the new token) runs
          first and the HBM cache scatter is a SEPARATE task depending only
          on k/v — the scoreboard defers it behind later-ready work.
        * ``self._paged``: the cache tasks take ``input:active`` +
          ``input:tables`` data operands and scatter/walk the block pool
          (scatter must precede the walk — a paged write has no in-VMEM
          splice to hide behind, so the classic chain order stands).
        """
        g = self.graph
        if self._paged:
            g.add(Task(f"cache_update{tag}", "cache_update",
                       (f"v:k{tag}", f"v:v{tag}", kc_in, vc_in, "input:lengths",
                        "input:active", "input:tables"),
                       (f"v:kc2{tag}", f"v:vc2{tag}")))
            g.add(Task(f"flash_decode{tag}", "flash_decode",
                       (f"v:q{tag}", f"v:kc2{tag}", f"v:vc2{tag}", "input:lengths",
                        "input:active", "input:tables"),
                       (f"v:attn{tag}",)))
            g.add(Task(f"o_proj_ar{tag}", "linear_allreduce",
                       (f"v:attn{tag}", "param:wo"), (f"v:attn_out{tag}",)))
            g.add(Task(f"resid1{tag}", "add", (x_in, f"v:attn_out{tag}"), (f"v:x1{tag}",)))
            return
        if split_sweep:
            g.add(Task(f"flash_decode{tag}", "flash_decode_append",
                       (f"v:q{tag}", f"v:k{tag}", f"v:v{tag}", kc_in, vc_in,
                        "input:lengths"),
                       (f"v:attn{tag}",)))
            g.add(Task(f"o_proj_ar{tag}", "linear_allreduce",
                       (f"v:attn{tag}", "param:wo"), (f"v:attn_out{tag}",)))
            g.add(Task(f"resid1{tag}", "add", (x_in, f"v:attn_out{tag}"), (f"v:x1{tag}",)))
            g.add(Task(f"cache_update{tag}", "cache_update",
                       (f"v:k{tag}", f"v:v{tag}", kc_in, vc_in, "input:lengths"),
                       (f"v:kc2{tag}", f"v:vc2{tag}")))
            return
        g.add(Task(f"cache_update{tag}", "cache_update",
                   (f"v:k{tag}", f"v:v{tag}", kc_in, vc_in, "input:lengths"),
                   (f"v:kc2{tag}", f"v:vc2{tag}")))
        g.add(Task(f"flash_decode{tag}", "flash_decode",
                   (f"v:q{tag}", f"v:kc2{tag}", f"v:vc2{tag}", "input:lengths"),
                   (f"v:attn{tag}",)))
        g.add(Task(f"o_proj_ar{tag}", "linear_allreduce",
                   (f"v:attn{tag}", "param:wo"), (f"v:attn_out{tag}",)))
        g.add(Task(f"resid1{tag}", "add", (x_in, f"v:attn_out{tag}"), (f"v:x1{tag}",)))

    def make_mlp_block(self, *, tag: str = ""):
        g = self.graph
        g.add(Task(f"ln2{tag}", "rmsnorm", (f"v:x1{tag}", "param:ln2"), (f"v:xn2{tag}",)))
        g.add(Task(f"gate_up{tag}", "linear", (f"v:xn2{tag}", "param:mlp_gate", "param:mlp_up"), (f"v:gu{tag}",)))
        g.add(Task(f"swiglu{tag}", "swiglu", (f"v:gu{tag}",), (f"v:h{tag}",)))
        g.add(Task(f"down{tag}", "linear", (f"v:h{tag}", "param:mlp_down"), (f"v:mlp_partial{tag}",)))
        g.add(Task(f"mlp_ar{tag}", "allreduce", (f"v:mlp_partial{tag}",), (f"v:mlp_out{tag}",)))
        g.add(Task(f"resid2{tag}", "add", (f"v:x1{tag}", f"v:mlp_out{tag}"), (f"v:x2{tag}",)))

    def make_moe_block(self, *, tag: str = ""):
        """MoE variant of the MLP block: routed grouped-expert MLP + AR in
        one task. Lowered through TP_MoE / the fused routed-experts kernel
        by default, or through the builder's ``moe_impl`` callback (the EP
        model's router → LL a2a dispatch → grouped GEMM → combine path)."""
        g = self.graph
        g.add(Task(f"ln2{tag}", "rmsnorm", (f"v:x1{tag}", "param:ln2"), (f"v:xn2{tag}",)))
        g.add(Task(
            f"moe{tag}", "moe",
            (f"v:xn2{tag}", "param:router", "param:mlp_gate", "param:mlp_up",
             "param:mlp_down"),
            (f"v:mlp_out{tag}",),
        ))
        g.add(Task(f"resid2{tag}", "add", (f"v:x1{tag}", f"v:mlp_out{tag}"), (f"v:x2{tag}",)))

    def _record_layer(self, i: int):
        tag = f"@{i}"
        x_in = "input:x" if i == 0 else f"v:x2@{i - 1}"
        kc_in = "input:kc" if i == 0 else f"v:kc2@{i - 1}"
        vc_in = "input:vc" if i == 0 else f"v:vc2@{i - 1}"
        self.make_attn_front(tag=tag, x_in=x_in)
        self.make_attn_back(tag=tag, x_in=x_in, kc_in=kc_in, vc_in=vc_in,
                            split_sweep=not self._paged)
        if getattr(self.config, "is_moe", False):
            self.make_moe_block(tag=tag)
        else:
            self.make_mlp_block(tag=tag)

    def _publish_schedule_stats(self):
        """Emit the scheduler's ``tdt_mega_*`` series — from the builder,
        once per build: ``summary()`` re-runs ``schedule``, so emitting
        inside the scheduler would double-count every audit call."""
        from triton_dist_tpu.runtime import telemetry

        st = self.graph.stats
        policy = str(st.get("policy", self.schedule_policy))
        telemetry.inc("tdt_mega_tasks_scheduled_total",
                      float(st.get("tasks", 0)), policy=policy)
        telemetry.inc("tdt_mega_fusion_hits_total",
                      float(st.get("fusion_hits", 0)), policy=policy)
        telemetry.set_gauge("tdt_mega_ready_depth",
                            float(st.get("max_ready_depth", 1)), policy=policy)

    # --------------------------------------------------------------- codegen
    def build_layer_fn(self):
        """Schedule the recorded graph (recording the standard layer if the
        graph is empty) and return ``layer_fn(lp, x, ks, vs, li, lengths) ->
        (x', ks, vs)`` assembled group-by-group from the schedule.
        Shard-local (inside shard_map over axis); caches are STACKED
        (L, B, Hkv, S, D) and updated in place via ``.at[li]`` (aliased
        under jit — a per-layer unstack/restack was measured to cost a full
        cache copy per token, 268 MB/step at ctx=4096)."""
        if not self.graph.tasks:
            self.make_attn_front()
            self.make_attn_back()
            if getattr(self.config, "is_moe", False):
                self.make_moe_block()
            else:
                self.make_mlp_block()
        groups = self.graph.schedule(policy=self.schedule_policy,
                                     cost_fn=self.group_cost)
        self._publish_schedule_stats()

        c = self.config
        hq = c.num_q_heads // self.world
        hkv = c.num_kv_heads // self.world
        hd = c.head_dim

        executors = []  # list of (env, lp) -> None closures
        self.plan = []
        for group in groups:
            gname = group[0].group.split(":")[0]
            ex = self._lower_group(gname, group, hq=hq, hkv=hkv, hd=hd)
            self.plan.append(f"{gname}→{ex.__name__}")
            executors.append(ex)

        # The layer's results are wherever the graph says they are: the last
        # task's first output is the residual stream, the cache_update
        # task's outputs are the updated caches.
        final_out = self.graph.tasks[-1].outputs[0]
        cu = next((t for t in self.graph.tasks if t.op == "cache_update"), None)
        if cu is None:
            raise ValueError(
                "megakernel graph must contain a cache_update task: "
                "build_layer_fn returns (residual, k_cache, v_cache) and "
                "reads the caches off that task's outputs. For attention-free "
                "graphs, lower the groups directly via _lower_group.")
        kc_out, vc_out = cu.outputs[0], cu.outputs[1]

        def layer_fn(lp, x, ks, vs, li, lengths):
            env = {"input:x": x, "input:pos": lengths, "input:lengths": lengths,
                   "input:kc": (ks, li), "input:vc": (vs, li)}
            for ex in executors:
                ex(env, lp)
            ks, _ = env[kc_out]
            vs, _ = env[vc_out]
            return env[final_out], ks, vs

        layer_fn.plan = tuple(self.plan)
        return layer_fn

    def build_step_fn(self, num_layers: int):
        """The serving-shaped persistent step: ALL ``num_layers`` layers
        recorded into ONE graph (``@<layer>``-suffixed tasks), scheduled as
        one unit — under the scoreboard policy, a layer's deferred cache
        scatter interleaves with the next layer's attn-front. Returns
        ``step_fn(layers, x, ks, vs, lengths, active=None, tables=None) ->
        (x', ks, vs)`` where ``layers`` is the pre-split per-layer param
        list (``split_layer_params``) and ks/vs are the stacked contiguous
        caches — or, with ``paged=True``, the stacked block POOLS, with
        ``tables`` (B, max_blocks) and ``active`` (B,) flowing as data so
        one compiled program covers every batch composition."""
        if self.graph.tasks:
            raise ValueError("build_step_fn records its own graph — use a fresh builder")
        for i in range(num_layers):
            self._record_layer(i)
        groups = self.graph.schedule(policy=self.schedule_policy,
                                     cost_fn=self.group_cost)
        self._publish_schedule_stats()

        c = self.config
        hq = c.num_q_heads // self.world
        hkv = c.num_kv_heads // self.world
        hd = c.head_dim

        executors = []  # (executor, layer_index) in emission order
        self.plan = []
        for group in groups:
            gname = group[0].group.split(":")[0]
            li = int(group[0].name.rsplit("@", 1)[1])
            ex = self._lower_group(gname, group, hq=hq, hkv=hkv, hd=hd, li=li)
            self.plan.append(f"{gname}@{li}→{ex.__name__}")
            executors.append((ex, li))

        last = num_layers - 1
        final_out = f"v:x2@{last}"
        kc_out, vc_out = f"v:kc2@{last}", f"v:vc2@{last}"
        paged = self._paged

        def step_fn(layers, x, ks, vs, lengths, active=None, tables=None):
            env = {"input:x": x, "input:pos": lengths, "input:lengths": lengths,
                   "input:kc": (ks, 0), "input:vc": (vs, 0)}
            if paged:
                if active is None or tables is None:
                    raise ValueError("paged step_fn needs active + tables operands")
                env["input:active"] = active
                env["input:tables"] = tables
            for ex, li in executors:
                ex(env, layers[li])
            ks, _ = env[kc_out]
            vs, _ = env[vc_out]
            return env[final_out], ks, vs

        step_fn.plan = tuple(self.plan)
        return step_fn

    def build_verify_fn(self, num_layers: int, k: int):
        """Speculative k-wide verify program: the persistent paged step
        graph of ``build_step_fn`` replayed ``k`` times inside ONE launch.
        Sub-step ``j`` scores column ``j`` of each slot's draft window at
        position ``lengths + min(j, steps)`` — ``steps`` (B,) is the
        per-slot participating width, flowing as DATA (like the masks and
        tables), so one compiled program covers every acceptance pattern,
        batch composition and adaptive-k backoff state; the jit cache above
        is keyed on ``k`` alone. Each sub-step's active mask is
        ``j < steps``: a non-participating slot's cache write redirects to
        the NULL block and its attention bound stays at its frozen length,
        exactly the non-speculative inactive-slot contract. Returns
        ``verify_fn(layers, xs (B, k, d), pk, pv, lengths, steps, tables)
        -> (x2 (B, k, d), pk, pv)``."""
        if not self._paged:
            raise ValueError("the verify program decodes the block pool: paged=True")
        step_fn = self.build_step_fn(num_layers)

        def verify_fn(layers, xs, pk, pv, lengths, steps, tables):
            outs = []
            for j in range(k):
                pos = lengths + jnp.minimum(jnp.int32(j), steps)
                x, pk, pv = step_fn(layers, xs[:, j], pk, pv, pos,
                                    active=j < steps, tables=tables)
                outs.append(x)
            return jnp.stack(outs, axis=1), pk, pv

        verify_fn.plan = step_fn.plan
        return verify_fn

    # ------------------------------------------------------ group lowering
    def _lower_group(self, gname: str, group, *, hq: int, hkv: int, hd: int,
                     li: int | None = None):
        """Return an executor closure for one fusion group (or one
        standalone task). Executors read/write the value environment.
        ``li`` binds the layer index at lowering time (the step graph's
        groups each belong to one layer); ``li=None`` reads it from the
        cache value tuples the per-layer ``layer_fn`` threads through."""
        c = self.config
        axis = self.axis
        # Snapshot like `axis`/`world`: executors must not pin the whole
        # builder in their closure chain nor track post-build mutation.
        mesh_axes = self.mesh_axes
        eps = c.rms_eps

        from triton_dist_tpu.kernels.flash_decode import (
            flash_decode, paged_flash_decode, paged_kv_append,
        )
        from triton_dist_tpu.kernels.gemm_allreduce import gemm_ar_shard
        from triton_dist_tpu.kernels.allreduce import AllReduceMethod, all_reduce_shard
        from triton_dist_tpu.layers.tp import apply_rope

        param = lambda name: name.split(":", 1)[1]

        def cache_li(env_li):
            return env_li if li is None else li

        # The fused executors consume the GROUP's recorded dataflow (task
        # inputs/outputs), same contract as the standalone lowerings — a
        # mutated graph that rebinds value names flows through both paths
        # identically instead of silently reading hardcoded keys.
        if gname == "attn_front":
            # [rmsnorm(x, ln), linear(·, w), head_norm(·, qn, kn), rope(·, pos)]
            ln_t, lin_t, hn_t, rope_t = group
            x_in, ln_p = ln_t.inputs[0], param(ln_t.inputs[1])
            w_p = param(lin_t.inputs[1])
            qn_p, kn_p = param(hn_t.inputs[1]), param(hn_t.inputs[2])
            pos_in = rope_t.inputs[1]
            out_q, out_k, out_v = rope_t.outputs

            def fused_attn_front(env, lp):
                x = env[x_in]
                b = x.shape[0]
                q, k, v = fused_ln_qkv_rope(
                    x, lp[ln_p], lp[w_p], lp[qn_p], lp[kn_p],
                    env[pos_in], num_q_heads=hq, num_kv_heads=hkv,
                    head_dim=hd, rope_theta=c.rope_theta, eps=eps,
                )
                env[out_q] = q.reshape(b, hq, hd)
                env[out_k] = k.reshape(b, hkv, hd)
                env[out_v] = v.reshape(b, hkv, hd)
            return fused_attn_front

        if gname == "attn_back" and self._paged:
            # [cache_update(k,v,pk,pv,len,active,tables), flash_decode(·),
            #  linear_allreduce(·, wo), add(x, ·)] — pool scatter + block-
            #  table walk + o-proj partial in one jit step (the walk is the
            #  Pallas kernel); AR + residual at graph level.
            cu_t, fd_t, oar_t, add_t = group
            k_in, v_in = cu_t.inputs[0], cu_t.inputs[1]
            kc_in, vc_in, len_in = cu_t.inputs[2], cu_t.inputs[3], cu_t.inputs[4]
            act_in, tab_in = cu_t.inputs[5], cu_t.inputs[6]
            q_in = fd_t.inputs[0]
            wo_p = param(oar_t.inputs[1])
            resid_in = (add_t.inputs[0] if add_t.inputs[1] == oar_t.outputs[0]
                        else add_t.inputs[1])
            kc_out, vc_out = cu_t.outputs
            out_v = add_t.outputs[0]
            world = self.world

            def fused_paged_attn_back_ex(env, lp):
                q = env[q_in]
                k_new, v_new = env[k_in], env[v_in]
                pk, env_li = env[kc_in]
                pv, _ = env[vc_in]
                lengths = env[len_in]
                li_ = cache_li(env_li)
                b = q.shape[0]
                partial, pk, pv = fused_paged_attn_back(
                    q, k_new, v_new, pk, pv, li_, env[tab_in], lengths,
                    env[act_in], lp[wo_p],
                )
                # Same rounding points as the contiguous back-leg (and as
                # gemm_ar_shard's decode ONE_SHOT path): cast the f32
                # partial to model dtype, then all-reduce.
                attn_out = partial.astype(q.dtype).reshape(b, -1)
                if world > 1:
                    attn_out = all_reduce_shard(
                        attn_out, axis=axis, mesh_axes=mesh_axes,
                        method=AllReduceMethod.ONE_SHOT,
                    )
                env[out_v] = env[resid_in] + attn_out
                env[kc_out] = (pk, li_)
                env[vc_out] = (pv, li_)
            return fused_paged_attn_back_ex

        if gname == "attn_back":
            # [cache_update(k,v,kc,vc,len), flash_decode(q,·,·,len),
            #  linear_allreduce(·, wo), add(x, ·)] — one fused kernel for the
            #  sweep + o-proj partial; AR + residual at graph level; the HBM
            #  cache append is an in-place scatter OFF the attention path.
            cu_t, fd_t, oar_t, add_t = group
            k_in, v_in = cu_t.inputs[0], cu_t.inputs[1]
            kc_in, vc_in, len_in = cu_t.inputs[2], cu_t.inputs[3], cu_t.inputs[4]
            q_in = fd_t.inputs[0]
            wo_p = param(oar_t.inputs[1])
            resid_in = (add_t.inputs[0] if add_t.inputs[1] == oar_t.outputs[0]
                        else add_t.inputs[1])
            kc_out, vc_out = cu_t.outputs
            out_v = add_t.outputs[0]
            world = self.world

            def fused_attn_back_ex(env, lp):
                q = env[q_in]
                k_new, v_new = env[k_in], env[v_in]
                ks, env_li = env[kc_in]
                vs, _ = env[vc_in]
                lengths = env[len_in]
                li_ = cache_li(env_li)
                b = q.shape[0]
                partial = fused_attn_back(
                    q, k_new, v_new, ks[li_], vs[li_], lengths, lp[wo_p],
                )  # (B, d_model) f32 o-proj partial
                # Same rounding points as gemm_ar_shard's decode (ONE_SHOT)
                # path: cast the partial to model dtype, then all-reduce.
                attn_out = partial.astype(q.dtype).reshape(b, -1)
                if world > 1:
                    # mesh_axes is LOAD-BEARING on multi-axis meshes: without
                    # it the one-shot kernel addresses peers by tp index as a
                    # GLOBAL device id and another dp group's puts land here
                    # (found by the dp x tp dryrun: leftover semaphore counts
                    # + rendezvous hang).
                    attn_out = all_reduce_shard(
                        attn_out, axis=axis, mesh_axes=mesh_axes,
                        method=AllReduceMethod.ONE_SHOT,
                    )
                env[out_v] = env[resid_in] + attn_out
                # The cache_update task's semantic outputs: one-row in-place
                # scatter per sequence, scheduled by XLA in parallel with
                # the fused sweep (which already folded the new token in).
                bids = jnp.arange(b)
                ks = ks.at[li_, bids, :, lengths].set(k_new)
                vs = vs.at[li_, bids, :, lengths].set(v_new)
                env[kc_out] = (ks, li_)
                env[vc_out] = (vs, li_)
            return fused_attn_back_ex

        if gname == "attn_sweep":
            # [flash_decode_append(q,k,v,kc,vc,len), linear_allreduce(·, wo),
            #  add(x, ·)] — the step graph's SPLIT back-leg: same fused
            #  kernel (in-VMEM splice of the new token, so it never waits on
            #  the HBM append), but the cache scatter is a separate task the
            #  scoreboard defers behind the next layer's front.
            fd_t, oar_t, add_t = group
            q_in, k_in, v_in = fd_t.inputs[0], fd_t.inputs[1], fd_t.inputs[2]
            kc_in, vc_in, len_in = fd_t.inputs[3], fd_t.inputs[4], fd_t.inputs[5]
            wo_p = param(oar_t.inputs[1])
            resid_in = (add_t.inputs[0] if add_t.inputs[1] == oar_t.outputs[0]
                        else add_t.inputs[1])
            out_v = add_t.outputs[0]
            world = self.world

            def fused_attn_sweep_ex(env, lp):
                q = env[q_in]
                k_new, v_new = env[k_in], env[v_in]
                ks, env_li = env[kc_in]
                vs, _ = env[vc_in]
                lengths = env[len_in]
                li_ = cache_li(env_li)
                b = q.shape[0]
                partial = fused_attn_back(
                    q, k_new, v_new, ks[li_], vs[li_], lengths, lp[wo_p],
                )
                attn_out = partial.astype(q.dtype).reshape(b, -1)
                if world > 1:
                    attn_out = all_reduce_shard(
                        attn_out, axis=axis, mesh_axes=mesh_axes,
                        method=AllReduceMethod.ONE_SHOT,
                    )
                env[out_v] = env[resid_in] + attn_out
            return fused_attn_sweep_ex

        if gname == "moe_block":
            t_task = group[0]
            x_in = t_task.inputs[0]
            out_v = t_task.outputs[0]
            if self.moe_impl is not None:
                # Caller-supplied MoE lowering — the EP model's router → LL
                # a2a dispatch → grouped GEMM → combine path becomes the
                # graph's moe task body (AUTO route resolved at trace time).
                impl = self.moe_impl

                def moe_impl_ex(env, lp):
                    env[out_v] = impl(lp, env[x_in])
                return moe_impl_ex
            # The routed-experts MLP through ONE Pallas kernel (fused
            # gate/up→SwiGLU→down, h never in HBM) — routing/dispatch, AR
            # and the weighted unpermute stay at graph level with TP_MoE's
            # exact rounding points (fp32 partials on the wire). BEYOND the
            # reference megakernel (dense-only). pin_standalone("moe")
            # falls back to the jit-level TP_MoE lowering.
            r_p, g_p, u_p, d_p = (param(i) for i in t_task.inputs[1:])
            world = self.world
            mesh_axes = self.mesh_axes

            def fused_moe_ex(env, lp):
                from triton_dist_tpu.layers.tp import MOE_CAPACITY_FACTOR
                from triton_dist_tpu.kernels.moe_utils import (
                    capacity_for, combine, dispatch, make_routing_plan,
                    topk_routing,
                )

                x = env[x_in]
                tkn = x.shape[0]
                n_e = lp[r_p].shape[1]
                logits = jnp.dot(x, lp[r_p], preferred_element_type=jnp.float32)
                idx, wts = topk_routing(logits, c.top_k)
                cap = capacity_for(tkn, c.top_k, n_e, MOE_CAPACITY_FACTOR)
                plan = make_routing_plan(idx, n_e, cap)
                xe = dispatch(x, plan)  # (E, C, d)
                y = fused_moe_block(xe, lp[g_p], lp[u_p], lp[d_p])
                out = combine(y, plan, wts, tkn, out_dtype=jnp.float32)
                if world > 1:
                    out = all_reduce_shard(
                        out, axis=axis, mesh_axes=mesh_axes,
                        method=AllReduceMethod.AUTO,
                    )
                env[out_v] = out.astype(x.dtype)
            return fused_moe_ex

        if gname == "mlp_block":
            # [rmsnorm(x1, ln), linear(·, wg, wu), swiglu, linear(·, wd)]
            ln_t, gu_t, _, dn_t = group
            x_in, ln_p = ln_t.inputs[0], param(ln_t.inputs[1])
            g_p, u_p = param(gu_t.inputs[1]), param(gu_t.inputs[2])
            d_p = param(dn_t.inputs[1])
            out_v = dn_t.outputs[0]

            def fused_mlp(env, lp):
                env[out_v] = fused_mlp_block(
                    env[x_in], lp[ln_p], lp[g_p], lp[u_p], lp[d_p], eps=eps,
                )
            return fused_mlp

        # ----- standalone lowerings (unmatched tasks) -----
        task = group[0]
        op = task.op

        if op == "rmsnorm":
            def standalone_rmsnorm(env, lp, t=task):
                x = env[t.inputs[0]]
                env[t.outputs[0]] = _rmsnorm_rows(
                    x.astype(jnp.float32), lp[param(t.inputs[1])], eps, x.dtype
                )
            return standalone_rmsnorm

        if op == "linear":
            def standalone_linear(env, lp, t=task):
                x = env[t.inputs[0]]
                ws = [lp[param(i)] for i in t.inputs[1:]]
                outs = [
                    jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
                    for w in ws
                ]
                env[t.outputs[0]] = outs[0] if len(outs) == 1 else jnp.concatenate(outs, -1)
            return standalone_linear

        if op == "head_norm":
            def standalone_head_norm(env, lp, t=task):
                qkv = env[t.inputs[0]]
                b = qkv.shape[0]
                h3 = qkv.reshape(b, hq + 2 * hkv, hd)
                qn = lp[param(t.inputs[1])]
                kn = lp[param(t.inputs[2])]
                q = _rmsnorm_rows(h3[:, :hq].astype(jnp.float32), qn, eps, qkv.dtype)
                k = _rmsnorm_rows(
                    h3[:, hq : hq + hkv].astype(jnp.float32), kn, eps, qkv.dtype
                )
                env[t.outputs[0]] = jnp.concatenate(
                    [q, k, h3[:, hq + hkv :]], axis=1
                ).reshape(b, -1)
            return standalone_head_norm

        if op == "rope":
            def standalone_rope(env, lp, t=task):
                qkv = env[t.inputs[0]]
                b = qkv.shape[0]
                pos = env[t.inputs[1]]
                h3 = qkv.reshape(b, hq + 2 * hkv, hd)
                # apply_rope wants (B, H, S, D) + pos (B, S): decode is S=1
                # (exactly TP_Attn.decode's q[:, :, 0] convention).
                rot = lambda u: apply_rope(
                    u[:, :, None, :], pos[:, None], c.rope_theta
                )[:, :, 0]
                env[t.outputs[0]] = rot(h3[:, :hq])
                env[t.outputs[1]] = rot(h3[:, hq : hq + hkv])
                env[t.outputs[2]] = h3[:, hq + hkv :]
            return standalone_rope

        if op == "cache_update" and self._paged:
            def standalone_cache_update_paged(env, lp, t=task):
                k_new, v_new = env[t.inputs[0]], env[t.inputs[1]]
                pk, env_li = env[t.inputs[2]]
                pv, _ = env[t.inputs[3]]
                lengths = env[t.inputs[4]]
                active = env[t.inputs[5]]
                tables = env[t.inputs[6]]
                li_ = cache_li(env_li)
                # Through the table, inactive slots to the NULL block, a
                # quantized row quantized once: ``paged_kv_append``.
                pk, pv = paged_kv_append(
                    pk, pv, li_, k_new, v_new, tables, lengths, active
                )
                env[t.outputs[0]] = (pk, li_)
                env[t.outputs[1]] = (pv, li_)
            return standalone_cache_update_paged

        if op == "cache_update":
            def standalone_cache_update(env, lp, t=task):
                k_new, v_new = env[t.inputs[0]], env[t.inputs[1]]
                ks, env_li = env[t.inputs[2]]
                vs, _ = env[t.inputs[3]]
                lengths = env[t.inputs[4]]
                li_ = cache_li(env_li)
                bids = jnp.arange(k_new.shape[0])
                ks = ks.at[li_, bids, :, lengths].set(k_new)
                vs = vs.at[li_, bids, :, lengths].set(v_new)
                env[t.outputs[0]] = (ks, li_)
                env[t.outputs[1]] = (vs, li_)
            return standalone_cache_update

        if op == "flash_decode" and self._paged:
            def standalone_paged_flash_decode(env, lp, t=task):
                q = env[t.inputs[0]]
                pk, env_li = env[t.inputs[1]]
                pv, _ = env[t.inputs[2]]
                lengths = env[t.inputs[3]]
                active = env[t.inputs[4]]
                tables = env[t.inputs[5]]
                li_ = cache_li(env_li)
                b = q.shape[0]
                step = active.astype(lengths.dtype)
                # The cache_update task already appended (quantize-once); a
                # quantized walk dequantizes in-kernel via the scale pool.
                out = paged_flash_decode(
                    q, pk, pv, tables, lengths + step, layer=li_,
                )
                env[t.outputs[0]] = out.reshape(b, hq * hd)
            return standalone_paged_flash_decode

        if op == "flash_decode":
            def standalone_flash_decode(env, lp, t=task):
                q = env[t.inputs[0]]
                ks, env_li = env[t.inputs[1]]
                vs, _ = env[t.inputs[2]]
                lengths = env[t.inputs[3]]
                li_ = cache_li(env_li)
                b = q.shape[0]
                env[t.outputs[0]] = flash_decode(
                    q, ks[li_], vs[li_], lengths + 1,
                ).reshape(b, hq * hd)
            return standalone_flash_decode

        if op == "flash_decode_append":
            def standalone_flash_decode_append(env, lp, t=task):
                # Append-then-attend on a COPY of the layer slice — the
                # bitwise oracle for the fused sweep's in-VMEM splice (the
                # real HBM append stays the cache_update task's job).
                q = env[t.inputs[0]]
                k_new, v_new = env[t.inputs[1]], env[t.inputs[2]]
                ks, env_li = env[t.inputs[3]]
                vs, _ = env[t.inputs[4]]
                lengths = env[t.inputs[5]]
                li_ = cache_li(env_li)
                b = q.shape[0]
                bids = jnp.arange(b)
                kl = ks[li_].at[bids, :, lengths].set(k_new)
                vl = vs[li_].at[bids, :, lengths].set(v_new)
                env[t.outputs[0]] = flash_decode(
                    q, kl, vl, lengths + 1,
                ).reshape(b, hq * hd)
            return standalone_flash_decode_append

        if op == "linear_allreduce":
            def standalone_linear_ar(env, lp, t=task):
                # mesh_axes as in the fused-path ARs: at decode sizes the
                # AUTO route picks the fused ll_one_shot GEMM-AR kernel,
                # whose peer addressing needs the full axis list on
                # multi-axis meshes.
                env[t.outputs[0]] = gemm_ar_shard(
                    env[t.inputs[0]], lp[param(t.inputs[1])], axis=axis,
                    mesh_axes=mesh_axes,
                )
            return standalone_linear_ar

        if op == "add":
            def standalone_add(env, lp, t=task):
                env[t.outputs[0]] = env[t.inputs[0]] + env[t.inputs[1]]
            return standalone_add

        if op == "swiglu":
            def standalone_swiglu(env, lp, t=task):
                gu = env[t.inputs[0]].astype(jnp.float32)
                g, u = jnp.split(gu, 2, axis=-1)
                env[t.outputs[0]] = (jax.nn.silu(g) * u).astype(env[t.inputs[0]].dtype)
            return standalone_swiglu

        if op == "allreduce":
            def standalone_allreduce(env, lp, t=task):
                # Output dtype follows the task's own input value, not a
                # hardcoded env key — a graph with renamed inputs lowers fine.
                # mesh_axes as in the attention AR: multi-axis peer
                # addressing needs the full axis list.
                x = env[t.inputs[0]]
                env[t.outputs[0]] = all_reduce_shard(
                    x.astype(jnp.float32), axis=axis,
                    mesh_axes=mesh_axes, method=AllReduceMethod.AUTO,
                ).astype(x.dtype)
            return standalone_allreduce

        if op == "moe":
            if self.moe_impl is not None:
                impl = self.moe_impl

                def standalone_moe_impl(env, lp, t=task):
                    env[t.outputs[0]] = impl(lp, env[t.inputs[0]])
                return standalone_moe_impl

            from triton_dist_tpu.layers.tp import MOE_CAPACITY_FACTOR, TP_MoE

            mesh_axes = self.mesh_axes

            def standalone_moe(env, lp, t=task):
                moe = TP_MoE(
                    w_router=lp[param(t.inputs[1])],
                    w_gate=lp[param(t.inputs[2])],
                    w_up=lp[param(t.inputs[3])],
                    w_down=lp[param(t.inputs[4])],
                    top_k=c.top_k,
                    capacity_factor=MOE_CAPACITY_FACTOR, axis=axis,
                    mesh_axes=mesh_axes,
                )
                env[t.outputs[0]] = moe(env[t.inputs[0]], mode="dist_ar")
            return standalone_moe

        raise NotImplementedError(f"no lowering for task op {op!r}")
