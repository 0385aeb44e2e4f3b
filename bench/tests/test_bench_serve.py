"""The serving cell on the CPU at a small size, with the device guard off:
traffic from the mix and the seed alone, a sound run is correct, and ``correct`` comes
out false with the timed path broken underneath and with the control (the
reference in float8) in the program's place.  The configuration's published
choices reach the program's ``ArchConfig`` where it has a field for them,
and a choice it cannot express is refused at set-up."""
import dataclasses
import json
import time

import numpy as np
import pytest

from bench import harness
from bench.drivers import serve as S
from bench.reference import moe_lm as ref
from bench.run import measure

V5E = harness.load_json(harness.BENCH / "peaks.json")["devices"][
    "TPU v5 lite"]
CHAT = "serve.qwen3-30b-a3b.chat"
SEED = 2 ** 31 + 2 ** 30 + 1234
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16,
         "moe_intermediate_size": 32, "intermediate_size": 32,
         "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 256,
         "num_hidden_layers": 2, "torch_dtype": "float32",
         "serving": {"num_slots": 4, "max_seq": 64, "scan_chunk": 4,
                     "prefill_chunk_tokens": 16, "greedy": True}}


def _cell(rate=12.0, **config):
    cell = harness.find_cell(harness.load_benchmark(), CHAT)
    cell.config = {**cell.config, **SMALL, **config}
    cell.traffic = {**cell.traffic,
                    "rate_per_s": rate,
                    "prompt_len": {"median": 12, "sigma": 1.0, "min": 2,
                                   "max": 40},
                    "output_len": {"median": 6, "sigma": 1.0, "min": 2,
                                   "max": 20},
                    "check_tokens": 40, "trace_s": 0.2}
    return cell


def _run(cell, seconds=1.0, trace=False):
    line = measure(cell, SEED, seconds, trace, time.perf_counter(),
                   device_guard=False, peaks=V5E)
    return json.loads(line)


# ------------------------------------------------------------------ traffic

def test_one_seed_gives_one_schedule():
    tr = _cell().traffic
    a = S.schedule(tr, 5.0, 77, 256)
    b = S.schedule(tr, 5.0, 77, 256)
    c = S.schedule(tr, 5.0, 78, 256)
    assert a.n == b.n == c.n == 60
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.max_new, b.max_new)
    assert all(np.array_equal(p, q) for p, q in zip(a.prompts, b.prompts))
    # another seed: the same arrivals and lengths in the same order, other
    # prompt ids
    assert np.array_equal(a.due, c.due)
    assert np.array_equal(a.max_new, c.max_new)
    assert list(map(len, a.prompts)) == list(map(len, c.prompts))
    assert not all(np.array_equal(p, q) for p, q in zip(a.prompts, c.prompts))
    # the mix's order seed puts the same sets in another order
    d = S.schedule({**tr, "order_seed": tr["order_seed"] + 1}, 5.0, 77, 256)
    assert not np.array_equal(a.max_new, d.max_new)
    assert np.allclose(np.sort(np.diff(a.due, prepend=0.0)),
                       np.sort(np.diff(d.due, prepend=0.0)))
    assert sorted(a.max_new) == sorted(d.max_new)
    assert 0 < a.due[0] and a.due[-1] < 5.0
    assert all(2 <= len(p) <= 40 for p in a.prompts)
    assert a.max_new.min() >= 2 and a.max_new.max() <= 20


def test_the_mix_fits_the_slots():
    cfg = harness.find_cell(harness.load_benchmark(), CHAT).config
    tr = harness.load_json(harness.BENCH / "traffic" / "serve-chat.json")
    assert (tr["prompt_len"]["max"] + tr["output_len"]["max"]
            <= cfg["serving"]["max_seq"])
    assert tr["rate_per_s"] > 0 and tr["drain_s"] > 0


# ------------------------------------------------------------------ correct

def test_sound_runs_are_correct():
    out = _run(_cell())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 12
    assert set(out["metrics"]) == {m["name"] for m in _cell().end_to_end}
    assert any(k.startswith("request_p") for k in out["metrics"])
    assert set(out["checks"]) == set(_cell().limits)
    assert list(out)[-1] == "checks"
    # traced: the same comparison, and the per-layer metrics in place of
    # the end-to-end ones
    out = _run(_cell(), trace=True)
    assert out["correct"], out["checks"]
    assert not set(out["metrics"]) & {m["name"] for m in _cell().end_to_end}


def _stale_cache(monkeypatch):
    """A decode step that returns its K/V cache unchanged."""
    from repro.serve import engine
    orig = engine.decode_slots

    def unchanged(cfg, params, tok, cache, pos, **kw):
        return orig(cfg, params, tok, cache, pos, **kw)[0], cache
    monkeypatch.setattr(engine, "decode_slots", unchanged)


def _half_the_batch(monkeypatch):
    """A decode step that computes half of the slots and hands their
    logits to the other half."""
    from repro.serve import engine
    orig = engine.decode_slots

    def half(cfg, params, tok, cache, pos, **kw):
        logits, cache = orig(cfg, params, tok, cache, pos, **kw)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:logits.shape[0] - h]), cache
    monkeypatch.setattr(engine, "decode_slots", half)


def _altered_token(monkeypatch):
    """Every decoded token one id off where the logits are made."""
    import jax.numpy as jnp
    from repro.serve import engine
    orig = engine.decode_slots

    def shifted(cfg, params, tok, cache, pos, **kw):
        logits, cache = orig(cfg, params, tok, cache, pos, **kw)
        return jnp.roll(logits, 1, axis=-1), cache
    monkeypatch.setattr(engine, "decode_slots", shifted)


def _qk_norm_over_the_projection(monkeypatch):
    """q and k normalised over the whole projection where the
    configuration states each head (OLMoE's scope in Qwen3's place)."""
    import jax
    import jax.numpy as jnp
    from repro.models import attention

    def rms_norm(x, weight, eps=1e-5):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=(-2, -1), keepdims=True)
        return (x32 * jax.lax.rsqrt(var + eps)
                * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)
    monkeypatch.setattr(attention, "rms_norm", rms_norm)


def _gate_not_renormalised(monkeypatch):
    """The top-k gate served as the router gives it, where the configuration
    states renormalisation: each token's expert output scaled by the sum
    of its top-k probabilities."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer
    orig = transformer.moe_forward

    def unnormalised(cfg, params, x):
        out, aux = orig(cfg, params, x)
        probs = jax.nn.softmax(x.astype(jnp.float32) @ params["router"], -1)
        mass = jax.lax.top_k(probs, cfg.experts_per_token)[0].sum(
            -1, keepdims=True)
        return (out * mass).astype(out.dtype), aux
    monkeypatch.setattr(transformer, "moe_forward", unnormalised)


@pytest.mark.parametrize("fault", [_stale_cache, _half_the_batch,
                                   _altered_token,
                                   _qk_norm_over_the_projection,
                                   _gate_not_renormalised])
def test_faults_are_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = _run(_cell(rate=120.0))    # every slot in use
    assert not out["correct"], out["checks"]


def test_the_control_fails_the_limits(monkeypatch):
    """The reference in float8 in the program's place: its first choices
    read as served tokens, through the harness's own run."""
    orig = S.compare
    monkeypatch.setattr(S, "compare", lambda *a, **k: {
        "program": orig(*a, **{**k, "control": True})["control"]})
    monkeypatch.setattr(harness, "load_driver", lambda kind: S)
    out = _run(_cell(torch_dtype="bfloat16"))
    assert not out["correct"], out["checks"]


# ------------------------------------------------------------------ choices

# the program's fields for the choices ``serve.CHOICES`` maps
CHOICE_FIELDS = ("norm_topk_prob", "qk_norm_scope")
OLMOE_CHOICES = {"model_type": "olmoe", "norm_topk_prob": False}


def _without_the_choices():
    """A stand-in ``ArchConfig`` with no field for either choice."""
    from repro.models.config import ArchConfig
    return dataclasses.make_dataclass("ArchConfig", [
        (f.name, f.type, dataclasses.field(
            default=f.default, default_factory=f.default_factory))
        for f in dataclasses.fields(ArchConfig)
        if f.name not in CHOICE_FIELDS], frozen=True)


def _with_the_choices():
    """A stand-in ``ArchConfig`` with a field for each choice, which reads
    ``None`` unless a value is passed."""
    from repro.models.config import ArchConfig
    return dataclasses.make_dataclass(
        "ArchConfig", [(f, object, None) for f in CHOICE_FIELDS],
        bases=(ArchConfig,), frozen=True)


def test_the_chat_configuration_is_served_as_before():
    """The chat cell's ``ArchConfig`` is the one its driver built from
    literal choices before the table: the same programs and compile-cache
    keys."""
    from repro.models.config import ArchConfig
    cfg = harness.find_cell(harness.load_benchmark(), CHAT).config
    assert S.program_config(cfg) == ArchConfig(
        name="qwen3-30b-a3b.serve-stage", family="moe", num_layers=8,
        d_model=2048, num_heads=32, num_kv_heads=4, head_dim=128, d_ff=768,
        vocab_size=151936, rope_theta=1e6, qk_norm=True, activation="silu",
        num_experts=128, experts_per_token=8, num_shared_experts=0,
        norm_eps=1e-6, tie_embeddings=False, dtype="bfloat16")


@pytest.mark.parametrize("departure", [
    {"norm_topk_prob": False}, OLMOE_CHOICES])
def test_a_choice_the_program_cannot_express_is_refused(monkeypatch,
                                                        departure):
    """Under a program whose ``ArchConfig`` has no field for the gate's
    renormalisation or the q/k norm's scope, a configuration stating
    OLMoE's choices (no renormalisation; q/k norms over the projections)
    is refused at set-up, before any weights are made, naming each
    choice."""
    from repro.models import config
    monkeypatch.setattr(config, "ArchConfig", _without_the_choices())
    made = []
    monkeypatch.setattr(S, "make_params", lambda *a: made.append(a))
    with pytest.raises(harness.BenchError) as e:
        _run(_cell(**departure))
    msg = str(e.value)
    assert "qwen3-30b-a3b.serve-stage" in msg
    assert "norm_topk_prob (top-k gate renormalised) is False" in msg
    assert ("'qk_norm_scope'" in msg) == ("model_type" in departure)
    assert not made


@pytest.mark.parametrize("choices, want", [
    ({}, (True, "head")), (OLMOE_CHOICES, (False, "projection"))])
def test_the_choices_reach_a_program_that_has_the_fields(monkeypatch,
                                                         choices, want):
    from repro.models import config
    monkeypatch.setattr(config, "ArchConfig", _with_the_choices())
    pcfg = S.program_config({**_cell().config, **choices})
    assert (pcfg.norm_topk_prob, pcfg.qk_norm_scope) == want


# ------------------------------------------------------------------ reference

def _hf_state(a, w):
    """The reference's weights under ``transformers``' names."""
    import torch

    def t(x):
        return torch.tensor(np.asarray(x, np.float32))
    sd = {"model.embed_tokens.weight": t(w["embed"]),
          "model.norm.weight": t(w["final_norm"]),
          "lm_head.weight": t(np.asarray(w["head"]).T)}
    L = w["layers"]
    for i in range(a.layers):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = t(L["ln1"][i])
        sd[p + "post_attention_layernorm.weight"] = t(L["ln2"][i])
        for n in ("q", "k", "v", "o"):
            sd[p + f"self_attn.{n}_proj.weight"] = t(L["w" + n][i]).T
        sd[p + "self_attn.q_norm.weight"] = t(L["q_norm"][i])
        sd[p + "self_attn.k_norm.weight"] = t(L["k_norm"][i])
        sd[p + "mlp.gate.weight"] = t(L["router"][i]).T
        for e in range(a.experts):
            q = p + f"mlp.experts.{e}."
            sd[q + "gate_proj.weight"] = t(L["w_gate"][i][e]).T
            sd[q + "up_proj.weight"] = t(L["w_up"][i][e]).T
            sd[q + "down_proj.weight"] = t(L["w_down"][i][e]).T
    return sd


@pytest.mark.parametrize("model_type", ["qwen3_moe", "olmoe"])
def test_reference_matches_transformers(model_type):
    """The plain reference against ``transformers``' own implementation of
    each architecture, on the same random weights, in float32."""
    import jax.numpy as jnp
    import torch
    import transformers as T
    cfg = {**_cell().config, "model_type": model_type,
           "norm_topk_prob": model_type == "qwen3_moe"}
    a = ref.arch(cfg)
    # random weights in the program's layout, which the sizes alone set
    params = S.make_params(S.program_config(_cell().config), 5)
    w = S.reference_weights(a, params)
    common = dict(vocab_size=a.vocab, hidden_size=a.d,
                  num_hidden_layers=a.layers, num_attention_heads=a.heads,
                  num_key_value_heads=a.kv_heads, num_experts=a.experts,
                  num_experts_per_tok=a.top_k, norm_topk_prob=a.norm_topk,
                  rms_norm_eps=a.eps, rope_theta=a.theta,
                  tie_word_embeddings=False)
    if model_type == "qwen3_moe":
        hf = T.Qwen3MoeForCausalLM(T.Qwen3MoeConfig(
            head_dim=a.head_dim, moe_intermediate_size=a.ff,
            intermediate_size=a.ff, **common))
    else:
        hf = T.OlmoeForCausalLM(T.OlmoeConfig(intermediate_size=a.ff,
                                              **common))
    hf.load_state_dict(_hf_state(a, w))
    tokens = np.random.default_rng(0).integers(0, a.vocab, 24)
    with torch.no_grad():
        want = hf(torch.tensor(tokens)[None]).logits[0].numpy()
    got = np.asarray(ref.logits(a, w, jnp.asarray(tokens, jnp.int32),
                                jnp.arange(24)))
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))


# ------------------------------------------------------------------ readers

def test_serving_readers_on_a_known_trace():
    from types import SimpleNamespace
    from bench.trace_reduce import TraceSummary
    t = TraceSummary(
        window_s=2.0, busy_s=1.5, chips=1,
        op_s={"flash_decode_pallas.3": 0.1, "fusion": 0.5},
        module_s={"jit_chunk(1)": 0.4, "jit_chunk(2)": 0.3},
        module_ops={"jit_chunk(1)": ["flash_decode_pallas.3", "fusion"],
                    "jit_chunk(2)": ["fusion"]})
    counters = {"traced": {"stats": {"decode_steps": 16}}}
    run = SimpleNamespace(cell=SimpleNamespace(config=_cell().config),
                          trace=t, counters=counters, peaks=V5E, device={})
    read = {m: harness.load_metric(m)
            for m in ("decode_step_ms", "device_idle.serve")}
    assert read["decode_step_ms"](run) == pytest.approx(1e3 * 0.4 / 16)
    assert read["device_idle.serve"](run) == pytest.approx(25.0)
    # nothing traced: the readers stay silent
    quiet = SimpleNamespace(cell=run.cell, trace=None, counters={},
                            peaks=V5E, device={})
    assert all(f(quiet) is None for f in read.values())
