"""The real manifest's ``axk1`` files (build, counts, reference, the four new
readers) driven through the harness on the CPU by a toy twin:
``toy/BENCHMARK.latent_dense.json`` is the toy's manifest, which is not this
file's to edit, with one configuration, one cell and the metrics the real
cell lists appended, and the twin's configuration, mix and limits are files
beside the toy's. So the twin, like the real cell, is files and entries
alone. The timed path broken underneath (``TAMPERS``: the scale without
YaRN's ``mscale ** 2``, a decode step that sees its last rows only, the
plain rotary table, a held expert left out) has to come out as not correct;
a one-off process on the chip imports the same four and plants them under
the real cell through ``harness.run_cell(..., tamper=...)``."""

import dataclasses
import io
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

TWIN = REPO / "tests/benchmark/toy/BENCHMARK.latent_dense.json"
CELL = "toy-axk1.toy-longread"
REAL_CELL = "a.x-k1-ep16-d5.longread"
SEED = 2**31 + 4040  # the driver's seeds are large
NEW = ("latent_read_pct", "latent_decode_roofline", "latent_prefill_roofline",
       "latent_prefill_ms_per_chunk", "expert_rows_per_call", "prefill_chunks_per_join",
       "prefill_chunk_roofline")


def _run(trace, **kw):
    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(TWIN, CELL, SEED, 1.0, trace, jax.devices()[:1],
                              out=out, err=err, root=REPO, **kw)
    return result, {l["phase"]: l for l in map(json.loads, out.getvalue().splitlines()[:-1])}


def test_twin_is_the_toys_manifest_with_entries_appended():
    mine = json.loads(TWIN.read_text())
    toy = json.loads(TWIN.with_name("BENCHMARK.json").read_text())
    assert [m["name"] for m in mine["per_layer"][len(toy["per_layer"]):]] == list(NEW)
    assert dict(mine, configs=mine["configs"][:-1], workloads=mine["workloads"][:-1],
                per_layer=mine["per_layer"][:-len(NEW)]) == toy
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    judged = {m["name"]: m for m in real["per_layer"]}
    assert mine["per_layer"][-len(NEW):] == [
        {k: v for k, v in judged[n].items() if k != "workloads"} for n in NEW]
    # the architecture's files are the real manifest's own, found by name
    cell = harness.load_cell(TWIN, CELL, root=REPO)
    for kind, mod in (("build", cell.build), ("reference", cell.reference),
                      ("counts", cell.counts.architecture)):
        assert mod.__file__ == str(REPO / f"benchmark/{kind}/axk1.py")
    real_cell = harness.load_cell(REPO / "BENCHMARK.json", REAL_CELL)
    names = {e["name"] for e, _ in real_cell.per_layer}
    assert names >= set(NEW) | {"decode_roofline", "serve_mfu", "hbm_peak_pct"}
    assert not names & {"prefill_roofline", "dsa_selected_pct", "dsa_attend_ms_per_chunk",
                        "ssm_scan_roofline", "swa_attended_pct", "decode_rows_per_step"}
    # the four new entries list the new cell alone, and stand last
    assert [(m["name"], m["workloads"]) for m in real["per_layer"][-4:]] == [
        (n, [REAL_CELL]) for n in ("latent_decode_roofline", "latent_prefill_roofline",
                                   "latent_prefill_ms_per_chunk", "latent_read_pct")]


def test_real_configuration_is_the_published_one_cut_as_stated():
    """Every number of the catalog's row under the same key but the two
    reduced; the program's configuration at the published widths; the
    ``bytes`` block is the built model's."""
    cell = harness.load_cell(REPO / "BENCHMARK.json", REAL_CELL)
    cfg = cell.cfg
    published = {"hidden_size": 7168, "intermediate_size": 18432, "moe_intermediate_size": 2048,
                 "num_attention_heads": 64, "q_lora_rank": 1536, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "num_experts_per_tok": 8, "vocab_size": 163840, "first_k_dense_replace": 1,
                 "n_group": 8, "topk_group": 4, "topk_method": "none", "rope_theta": 10000,
                 "max_position_embeddings": 131072, "routed_scaling_factor": 2.5}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
                                   "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                                   "type": "yarn"}
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["experts_held"]) == (5, 12, [0, 12])
    assert cfg["published"]["num_hidden_layers"] == 61 and cfg["published"]["n_routed_experts"] == 192
    mc = cell.build.model_config(cfg)
    assert (mc.num_layers, mc.latent_row, mc.cache_row, mc.num_experts, mc.experts_held) == (
        5, 576, 640, 192, (0, 12))
    assert mc.index_kinds == ("none",) * 5 and mc.index_layers == () and not mc.router_bias
    assert mc.mlp_kinds == ("dense",) + ("experts",) * 4
    s = cell.reference.sizes(cfg)
    n = lambda l: sum(int(np.prod(shape)) for _, shape, _ in cell.reference.layer_tensors(s, l))
    b = cfg["bytes"]
    assert (n(0), n(1)) == (b["layer_0_params"], b["layers_1_to_4_params_each"])
    held = n(0) + 4 * n(1) + b["embedding_and_head_params"] + b["norm_params"]
    assert held == b["params_held"] and 5.5e9 < held < 5.6e9
    sv = cfg["serving"]
    assert b["pool_bytes"] == sv["slots"] * sv["max_len"] * 5 * mc.cache_row * 2
    assert 0.6 < b["resident_bytes"] / 16e9 < 0.95
    # the counts know the mix's sizes: a 32k prompt is most of a second of the chip's peak
    assert 150e12 < cell.counts.prefill(cfg, 32768)["flops"] < 220e12
    row = cell.counts.latent_decode(cfg, [1000])
    assert row["bytes"] == 1000 * 1152 + 64 * (576 * 2 + 512 * 4)


@pytest.mark.timeout(600)
def test_twin_cell_is_correct_and_reads_the_new_metrics():
    result, phases = _run(trace=True)
    assert result["correct"] is True and result["failed"] == 0
    assert phases["window"]["lowerings_in_window"] == 0
    assert not any(phases["window"]["zero_counters"].values())
    got = result["metrics"]
    # counters read anywhere; no share of a roofline is reported off the chip,
    # and the toy's shapes do not tile, so no kernel is in its trace
    assert {"latent_read_pct", "expert_rows_per_call", "prefill_chunks_per_join"} <= set(got)
    assert not {"latent_decode_roofline", "latent_prefill_roofline", "prefill_chunk_roofline",
                "latent_prefill_ms_per_chunk"} & set(got)
    # the gather path reads the table's 128 rows a slot for contexts of 48-102
    assert 120.0 < got["latent_read_pct"]["value"] < 270.0
    assert got["prefill_chunks_per_join"]["value"] > 1.5  # prompts of 48-96 in chunks of 32
    assert 0.0 < got["expert_rows_per_call"]["value"]


# ------------------------------------------------------------ planted faults


def _replace_config(model, engine, cls=None, **changed):
    c = model.config
    fields = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    model.config = (cls or type(c))(**{**fields, **changed})
    engine.rebuild(engine.backend)


def no_mscale(model, engine, server):
    """The softmax scale without YaRN's ``mscale ** 2``."""
    base = type(model.config)

    class Unscaled(base):
        @property
        def softmax_scale(self):
            return float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    _replace_config(model, engine, cls=dataclasses.dataclass(frozen=True)(Unscaled))


def decode_sees_last(n: int):
    """A decode step that attends to its last ``n`` rows only."""

    def tamper(model, engine, server):
        from triton_dist_tpu.kernels import latent_flash
        from triton_dist_tpu.layers import latent_sparse as ls

        def windowed(q_nope, q_rope, pool, layer, tables, lengths, w_uk, w_uv, c):
            bs = pool.shape[3]
            at = lengths[:, None] - 1 - jnp.arange(n, dtype=jnp.int32)[None, :]
            sel = jnp.maximum(at, 0)
            rows = pool[layer, jnp.take_along_axis(tables, sel // bs, axis=1), 0, sel % bs]
            return ls.attend_absorbed(q_nope, q_rope, rows, at >= 0, w_uk, w_uv, c)

        ls.attend_absorbed_paged = windowed
        latent_flash.decode_takes = lambda *a: True  # every shape goes through it
        engine.rebuild(engine.backend)

    return tamper


def plain_rope_table(model, engine, server):
    """The rotary's plain table in YaRN's place (the scale left as it is)."""
    y = model.config.rope_scaling

    @dataclasses.dataclass(frozen=True)
    class Plain(type(y)):
        def inv_freq(self, dim, theta):
            return theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    _replace_config(model, engine, rope_scaling=Plain(**dataclasses.asdict(y)))


def leave_out_an_expert(model, engine, server):
    layers = [dict(lp) for lp in model.params["layers"]]
    for lp in layers[1:]:
        lp["e_down"] = lp["e_down"].at[1].set(0.0)
    model.params = {**model.params, "layers": layers}


#: name -> tamper at the toy's sizes; the real cell's window is 2048 rows
TAMPERS = {"no_mscale": no_mscale, "decode_sees_last_16": decode_sees_last(16),
           "plain_rope_table": plain_rope_table, "held_expert_left_out": leave_out_an_expert}


@pytest.mark.slow
@pytest.mark.timeout(600)
@pytest.mark.parametrize("name", list(TAMPERS))
def test_twin_cell_broken_underneath_is_not_correct(name, monkeypatch):
    from triton_dist_tpu.kernels import latent_flash
    from triton_dist_tpu.layers import latent_sparse as ls

    monkeypatch.setattr(ls, "attend_absorbed_paged", ls.attend_absorbed_paged)
    monkeypatch.setattr(latent_flash, "decode_takes", latent_flash.decode_takes)
    result, _ = _run(trace=False, tamper=TAMPERS[name])
    assert result["correct"] is False
    value, limit = result["compared"]["logit_gap"]
    assert value > 10 * limit
