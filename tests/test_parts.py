"""The device's time by the model's own parts, on the CPU: the name
(``obs/trace.py: part``), where it is entered (``models/``, ``kernels/``),
the map the runtime reads out of the compiled text (``runtime/executor.py``,
``TpuRuntime.program_parts``), the first-call layer that makes the text
askable, and the operator's capture (``agent/app.py``).

The two sides of the metadata test are two CHILD processes: a part entered
as a decorator is bound when its module is imported, so ``part`` is patched
to a null context before anything of ``models/`` or ``kernels/`` is. Each
child runs the ops at tiny widths through the public registry and writes the
compiled text of every program the runtime's keyed cache holds. No number
here is a device number."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agent_tpu.obs import trace as obs_trace
from agent_tpu.runtime import executor
from agent_tpu.runtime.runtime import TpuRuntime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LM = {"vocab_size": 3000, "d_model": 64, "d_ff": 96, "n_layers": 2,
      "dtype": "float32"}
MULTIPLIERS = {
    "embedding_multiplier": 5.656854249492381, "lm_head_multiplier": 0.0078125,
    "mlp_gate_multiplier": 0.5, "mlp_down_multiplier": 0.25,
    "attention_in_multiplier": 1.0, "attention_out_multiplier": 0.5,
    "key_multiplier": 0.5, "ssm_in_multiplier": 0.5,
    "ssm_out_multiplier": 0.25}
# family → (op, payload): the tiny configs of the four mixers' own test
# files; ``sparse_mla``'s holds 4 of 16 experts behind one dense layer.
FAMILIES = {
    "encoder": ("map_classify_tpu", {
        "texts": ["the quick brown fox", "lorem ipsum"] * 4, "topk": 3,
        "allow_fallback": False, "model_path": "parts-encoder",
        "model_config": {"d_model": 64, "n_heads": 4, "n_layers": 2,
                         "d_ff": 128, "max_len": 64, "n_classes": 10,
                         "dtype": "float32"}}),
    "power_retention": ("map_score_lm", {
        "model_path": "parts-retention", "model_config": {
            **LM, "n_heads": 10, "n_kv_heads": 2, "d_head": 16}}),
    "sparse_mla": ("map_score_lm", {
        "model_path": "parts-sparse", "model_config": {
            **LM, "n_heads": 4, "max_len": 163840, "mixer": "sparse_mla",
            "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 16,
            "index_head_dim": 16, "index_topk": 16, "rope_theta": 10000.0,
            "rope_factor": 40.0, "rope_original_max_len": 4096,
            "n_dense_layers": 1, "n_experts": 16, "n_experts_held": 4,
            "expert_first": 0, "n_experts_per_token": 4,
            "n_expert_groups": 4, "n_groups_per_token": 2, "d_expert": 32,
            "n_shared_experts": 1, "routed_scale": 2.5}}),
    "hybrid_ssm": ("map_score_lm", {
        "model_path": "parts-hybrid", "model_config": {
            **LM, "n_heads": 15, "n_kv_heads": 3, "d_head": 16,
            "max_len": 262144, "mixer": "hybrid_ssm", "rms_norm_eps": 1e-5,
            "rope_theta": 1e11, "ssm_n_heads": 6, "ssm_d_head": 16,
            "ssm_d_state": 24, "ssm_n_groups": 2, "ssm_d_conv": 4,
            "ssm_chunk": 128, **MULTIPLIERS}}),
    "dense_mla": ("map_score_lm", {
        "model_path": "parts-latent", "model_config": {
            **LM, "n_heads": 4, "max_len": 16384, "mixer": "dense_mla",
            "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0,
            "rope_factor": 8.0, "rope_original_max_len": 1500,
            "query_scale_beta": 0.1, "n_dense_layers": 0, "n_experts": 16,
            "n_experts_held": 4, "expert_first": 0, "n_experts_per_token": 4,
            "n_expert_groups": 1, "n_groups_per_token": 1, "d_expert": 32,
            "n_shared_experts": 1, "routed_scale": 1.0,
            "scoring_func": "softmax"}}),
}
# What every program of the family's op must name, between them.
FAMILY_PARTS = {
    "encoder": {"embed", "norm", "project", "mixer", "around", "ffn", "head"},
    "power_retention": {"embed", "norm", "project", "mixer", "around", "ffn",
                        "head"},
    "sparse_mla": {"embed", "norm", "project", "mixer", "around", "ffn",
                   "experts", "head"},
    "hybrid_ssm": {"embed", "norm", "project", "mixer", "around", "ffn",
                   "head"},
    "dense_mla": {"embed", "norm", "project", "mixer", "around", "ffn",
                  "experts", "head"},
}
# The CPU backend leaves the op's own iotas, compares and broadcasts (the
# length mask, ``rebuild_ids``) as instructions of their own: about a third
# of a tiny program's; on the chip they fuse.
NAMED_SHARE_FLOOR = 0.5

CHILD = r"""
import contextlib, json, os, sys
import jax
# An executable's cache key leaves metadata out: with the persistent cache
# on, one side would LOAD what the other compiled, scopes and all.
jax.config.update("jax_enable_compilation_cache", False)
out, null = sys.argv[1], sys.argv[2] == "null"
from agent_tpu.obs import trace
class Null(contextlib.ContextDecorator):
    def __enter__(self):
        return self
    def __exit__(self, *exc):
        return False
if null:
    trace.part = lambda name: Null()
from agent_tpu.ops import get_op
from agent_tpu.runtime.runtime import get_runtime, reset_runtime
families = json.loads(sys.argv[3])
for family, (op, payload) in families.items():
    reset_runtime()
    if op == "map_score_lm":
        payload = dict(payload, ids=[[(7 * i) % 2999 + 1 for i in range(300)]])
    result = get_op(op)(payload)
    assert result.get("ok") is True, result
    os.makedirs(os.path.join(out, family))
    texts = sorted(p.compiled_text() for p in get_runtime().cache.values()
                   if p.noted is not None)
    for i, text in enumerate(texts):
        with open(os.path.join(out, family, f"{i}.txt"), "w") as f:
            f.write(text)
"""


def strip_metadata(text: str) -> str:
    """Optimized HLO text without what a scope may change: every
    ``metadata={...}`` (braces matched outside quoted strings), the tables
    of source locations ahead of the first computation, and the numbers
    behind instruction names."""
    out, i = [], 0
    while True:
        j = text.find(", metadata={", i)
        if j < 0:
            out.append(text[i:])
            break
        out.append(text[i:j])
        k, depth, quoted = j + len(", metadata={"), 1, False
        while depth:
            c = text[k]
            if c == '"' and text[k - 1] != "\\":
                quoted = not quoted
            elif not quoted:
                depth += (c == "{") - (c == "}")
            k += 1
        i = k
    body = "".join(out)
    head, _, rest = body.partition("\n\n")
    tables = re.compile(
        r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n",
        re.S | re.M)
    # Names are numbered in order of appearance: the number XLA puts behind
    # a name counts the instructions of that name BEFORE inlining, and JAX
    # lowers a shared inner function (``jnp.where``'s) once a scope it is
    # called under, so a scope shifts the numbers and not one instruction.
    seen: dict = {}
    return re.sub(
        r"%[\w.\-]+",
        lambda m: seen.setdefault(m.group(0), f"%{len(seen)}"),
        head + "\n\n" + tables.sub("", rest + "\n\n"))


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    """family → side (``part`` as it is / patched to a null context) → the
    compiled texts of the op's programs, in a stable order."""
    base = tmp_path_factory.mktemp("parts")
    env = dict(os.environ, PYTHONPATH=ROOT, TASKS="*")
    children = {
        side: subprocess.Popen(
            [sys.executable, "-c", CHILD, str(base / side), side,
             json.dumps(FAMILIES)],
            cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for side in ("real", "null")}
    for side, child in children.items():
        tail = child.communicate(timeout=900)[0][-3000:]
        assert child.returncode == 0, (side, tail)
    return {
        family: {side: [(base / side / family / name).read_text()
                        for name in sorted(os.listdir(base / side / family))]
                 for side in children}
        for family in FAMILIES}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_scope_is_metadata_and_nothing_else(compiled, family):
    real, null = compiled[family]["real"], compiled[family]["null"]
    assert len(real) == len(null) >= 1
    assert any("part:" in text for text in real)
    assert not any("part:" in text for text in null)
    stripped = sorted(strip_metadata(t) for t in real)
    assert "part:" not in "".join(stripped)
    assert stripped == sorted(strip_metadata(t) for t in null)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_compiled_text_names_every_part_of_the_family(compiled, family):
    maps = [executor.parts_of_text(text) for text in compiled[family]["real"]]
    named = set()
    for module, parts in maps:
        assert module.startswith("jit_")
        named |= {p for p in parts["instructions"].values() if p}
        assert set(parts) == {"instructions", "mixed", "named_share"}
    assert named == FAMILY_PARTS[family]
    assert named <= set(obs_trace.PARTS)
    # The program that holds the layers: most of what can be a device event
    # has a part.
    layers = max(maps, key=lambda m: len(m[1]["instructions"]))[1]
    assert layers["named_share"] >= NAMED_SHARE_FLOOR


@pytest.mark.parametrize("family", sorted(set(FAMILIES) - {"encoder"}))
def test_an_instruction_inside_the_scanned_layer_body_has_a_part(compiled,
                                                                family):
    """The decoder family scans its layer stack: an instruction of the
    loop's BODY carries the scope it was traced under."""
    text = max(compiled[family]["real"], key=len)
    _, parts = executor.parts_of_text(text)
    inside = [m.group(1) for m in re.finditer(
        r"^\s+(?:ROOT )?%?([\w.\-]+) = .*op_name=\"[^\"]*/while/body/[^\"]*"
        r"part:[a-z]+", text, re.M)]
    assert inside
    assert all(parts["instructions"][name] for name in inside)


def test_the_innermost_scope_wins(compiled):
    """A shared expert inside the expert layer is ``ffn``; the multiplier
    inside a feed-forward is ``around``."""
    text = max(compiled["sparse_mla"]["real"], key=len)
    _, parts = executor.parts_of_text(text)
    nested = [m.group(1) for m in re.finditer(
        r"^\s+(?:ROOT )?%?([\w.\-]+) = (?!.* fusion\().*op_name=\"[^\"]*"
        r"part:experts/[^\"]*part:ffn/[^\"/]*\"", text, re.M)]
    assert nested
    assert {parts["instructions"][name] for name in nested} == {"ffn"}
    deeper = [m.group(1) for m in re.finditer(
        r"^\s+(?:ROOT )?%?([\w.\-]+) = (?!.* fusion\().*op_name=\"[^\"]*"
        r"part:ffn/[^\"]*part:around/[^\"/]*\"", text, re.M)]
    assert {parts["instructions"][name] for name in deeper} <= {"around"}


def test_the_expert_layer_on_the_kernel_path_is_all_experts(monkeypatch):
    """``held_experts_ffn`` at widths its kernels take (interpret mode, so
    each kernel is inlined under its own name): what sorts and counts, the
    pass that packs the rows, the grouped matmul with its row copies and the
    combine that reads them all lie under ``experts``, and under no other
    part; what is left without a part has no scope of the layer's at all
    (the compiler's own copies). On the chip each kernel is one custom call
    of that name: ``tests/test_tpu_compile.py`` holds the part map there."""
    from agent_tpu.kernels import grouped_ffn
    from agent_tpu.models import moe

    monkeypatch.setattr(grouped_ffn, "ROW_TILE", 64)
    sd = jax.ShapeDtypeStruct
    S, d, fe, held, k = 128, 256, 256, 4, 4
    bf = jnp.bfloat16
    text = jax.jit(lambda x, e, g, a, b, c: moe.held_experts_ffn(
        x, e, g, a, b, c, 0, pallas=True, interpret=True)).lower(
        sd((S, d), bf), sd((S, k), jnp.int32), sd((S, k), jnp.float32),
        sd((held, d, fe), bf), sd((held, d, fe), bf),
        sd((held, fe, d), bf)).compile().as_text()
    _, parts = executor.parts_of_text(text)
    assert {p for p in parts["instructions"].values() if p} == {"experts"}
    scoped = {m.group(1): m.group(2) for m in re.finditer(
        r"^\s+(?:ROOT )?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"", text, re.M)}
    for kernel in ("moe_pack_rows", "moe_grouped_swiglu",
                   "moe_combine_pairs"):
        inside = [i for i, op_name in scoped.items()
                  if f"part:experts/{kernel}/" in op_name]
        assert inside, kernel
        assert {parts["instructions"][i] for i in inside} == {"experts"}
    assert not [i for i, p in parts["instructions"].items()
                if p is None and "part:" in scoped.get(i, "")]


HAND_TEXT = '''HloModule jit_hand, is_scheduled=true

%fused_a (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(hand)/part:project/dot_general"}
  ROOT %add.1 = f32[8,8]{1,0} add(%dot.1, %p0), metadata={op_name="jit(hand)/part:around/add"}
}

%fused_b (p0: f32[8,8]) -> f32[8,8] {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  ROOT %sine.1 = f32[8,8]{1,0} sine(%p0.1), metadata={op_name="jit(hand)/part:ffn/jit(inner)/part:around/sin"}
}

%body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte = f32[8,8]{1,0} get-tuple-element(%arg), index=1
  %fusion.2 = f32[8,8]{1,0} fusion(%gte), kind=kLoop, calls=%fused_b, metadata={op_name="jit(hand)/part:ffn/jit(inner)/part:around/sin"}
  ROOT %tuple.1 = (s32[], f32[8,8]{1,0}) tuple(%gte, %fusion.2)
}

ENTRY %main (x: f32[8,8], w: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %w = f32[8,8]{1,0} parameter(1)
  %fusion.1 = f32[8,8]{1,0:T(8,128)(2,1)S(1)} fusion(%x, %w), kind=kOutput, calls=%fused_a, metadata={op_name="jit(hand)/part:around/add"}
  %copy.3 = f32[8,8]{1,0} copy(%fusion.1)
  %fusion.6 = f32[8,8]{1,0} fusion(%copy.3), kind=kLoop, calls=%fused_b
  %while.4 = (s32[], f32[8,8]{1,0}) while(%copy.3), condition=%cond, body=%body, metadata={op_name="jit(hand)/while"}
  ROOT %custom.5 = f32[8,8]{1,0} custom-call(%copy.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(hand)/part:mixer/pallas_call[name=k]"}
}
'''


def test_parts_of_a_hand_written_text():
    module, parts = executor.parts_of_text(HAND_TEXT)
    assert module == "jit_hand"
    got = parts["instructions"]
    # Innermost scope; a kernel's custom call; no scope, no part.
    assert got["fusion.2"] == got["sine.1"] == "around"
    assert got["custom.5"] == "mixer"
    assert got["while.4"] is None and got["copy.3"] is None
    # XLA wrote the ROOT's scope on the fusion; its one matmul is the
    # projection's, which is where its time goes.
    assert got["add.1"] == "around" and got["fusion.1"] == "project"
    assert parts["mixed"] == {"fusion.1": ["around", "project"]}
    # A fusion the compiler left without metadata is what its inside is.
    assert got["fusion.6"] == "around"
    # Events: fusion.1, copy.3, fusion.6, while.4, custom.5, fusion.2.
    assert parts["named_share"] == pytest.approx(4 / 6)


def test_an_unknown_part_raises_when_it_is_entered():
    assert len(obs_trace.PARTS) == len(set(obs_trace.PARTS)) == 8
    with pytest.raises(ValueError, match="unknown part 'attention'"):
        obs_trace.part("attention")

    def traced(x):
        with obs_trace.part("nope"):
            return x + 1

    with pytest.raises(ValueError):
        jax.jit(traced)(jnp.ones(2))


# ---- the first-call layer -------------------------------------------------

class Counting:
    """A jit wrapper that counts the calls that reach it."""

    def __init__(self, fn):
        self.jitted, self.calls = jax.jit(fn), 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.jitted(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return self.jitted.lower(*args, **kwargs)


def _ticks(name):
    from agent_tpu.obs.metrics import get_registry

    series = (get_registry().snapshot().get(name) or {}).get("series", [])
    return sum(s["value"] for s in series)


def test_the_first_call_notes_arguments_once_and_later_calls_reach_the_wrapper():
    runtime = TpuRuntime(devices=jax.devices()[:1])
    wrapper = Counting(lambda p, x, s: jnp.tanh(x @ p["w"]) * s)
    key = ("test_parts", "first_call")
    first = runtime.compiled(key, lambda: wrapper)
    assert isinstance(first, executor.Program) and first.noted is None
    assert first.compiled_text() is None
    assert runtime.program_parts() == {}          # nothing has run
    args = ({"w": jnp.ones((4, 4))}, np.ones((2, 4), np.float32), 2)
    before = _ticks("runtime_trace_lower_seconds_total")
    first(*args)
    assert _ticks("runtime_trace_lower_seconds_total") > before
    noted = first.noted
    assert noted[0][0]["w"].shape == (4, 4) and noted[0][1].dtype == np.float32
    assert noted[0][2].weak_type                   # the Python scalar
    # After it: the wrapper itself, and a kept layer passes straight through.
    assert runtime.compiled(key, lambda: 1 / 0) is wrapper
    after = _ticks("runtime_trace_lower_seconds_total")
    first(*args)
    first(*args)
    assert wrapper.calls == 3 and first.noted is noted
    assert _ticks("runtime_trace_lower_seconds_total") == after
    # Asking for the text compiles nothing: the call obtained the executable.
    executables = _ticks("runtime_xla_executables_total")
    parts = runtime.program_parts()
    assert _ticks("runtime_xla_executables_total") == executables
    (maps,) = parts.values()
    assert len(maps) == 1 and maps[0]["named_share"] == 0.0


def test_two_shapes_of_one_function_keep_both_maps():
    runtime = TpuRuntime(devices=jax.devices()[:1])

    def shaped(x):
        with obs_trace.part("ffn"):
            return jnp.sin(x) * 2

    for n in (4, 8):
        runtime.compiled(("test_parts", "shaped", n),
                         lambda: jax.jit(shaped))(jnp.ones((n,)))
    parts = runtime.program_parts()
    assert list(parts) == ["jit_shaped"] and len(parts["jit_shaped"]) == 2
    assert all("ffn" in m["instructions"].values()
               for m in parts["jit_shaped"])


def test_a_donated_argument_is_noted_before_it_is_gone():
    program = executor.Program(jax.jit(lambda s, x: s + x, donate_argnums=0))
    state = jnp.zeros((8,))
    program(state, jnp.ones((8,)))
    assert program.noted[0][0].shape == (8,)
    assert "HloModule" in program.compiled_text()


# ---- the operator's capture ----------------------------------------------

class Result:
    """What an op's thunk returns: ready only once it was blocked on."""

    def __init__(self, log):
        self.log = log

    def block_until_ready(self):
        self.log.append("result ready")
        return self


class FakeTrace:
    """``jax.profiler.trace``: notes when it closes, and leaves a trace."""

    def __init__(self, log, directory):
        self.log, self.directory = log, directory

    def __enter__(self):
        self.log.append("profiler open")

    def __exit__(self, *exc):
        self.log.append("profiler closed")
        where = os.path.join(self.directory, "plugins", "profile", "t0")
        os.makedirs(where, exist_ok=True)
        with open(os.path.join(where, "host.xplane.pb"), "wb") as f:
            f.write(b"\0")


class FakeRuntime:
    def program_parts(self):
        return {"jit_lm_segment": [{"instructions": {"fusion.1": "ffn",
                                                     "copy.2": None},
                                    "mixed": {}, "named_share": 0.5}]}


@pytest.fixture()
def agent(monkeypatch, tmp_path):
    from agent_tpu.agent.app import Agent
    from agent_tpu.config import AgentConfig, Config

    log = []
    monkeypatch.setattr(jax.profiler, "trace",
                        lambda directory: FakeTrace(log, directory))
    monkeypatch.setenv("PROFILE_CAPTURE_DIR", str(tmp_path))
    made = Agent(config=Config(agent=AgentConfig(
        controller_url="http://test", tasks=("echo",))), session=object(),
        runtime=FakeRuntime())
    return made, log


def test_a_capture_closes_after_the_result_is_ready_and_writes_the_parts(
        agent, tmp_path):
    made, log = agent
    made._pending_captures.append({"capture_id": "c1", "op": "echo"})
    result = made.profiled_call("echo", lambda: {"pending": [Result(log)]})
    assert isinstance(result["pending"][0], Result)
    assert log == ["profiler open", "result ready", "profiler closed"]
    (record,) = made._drain_capture_results()
    parts_file = record["summary"]["parts_file"]
    assert record["status"] == "done"
    assert parts_file == str(tmp_path / "capture-c1" / "plugins" / "profile"
                             / "t0" / "program_parts.json")
    with open(parts_file) as f:
        assert json.load(f) == FakeRuntime().program_parts()
    # ``n_trace_files`` counts it beside the trace.
    assert record["summary"]["n_trace_files"] == 2


def test_a_capture_of_an_op_that_raises_still_closes_and_reports(agent):
    made, log = agent
    made._pending_captures.append({"capture_id": "c2"})

    def thunk():
        raise RuntimeError("op failed")

    with pytest.raises(RuntimeError):
        made.profiled_call("echo", thunk)
    assert log == ["profiler open", "profiler closed"]
    (record,) = made._drain_capture_results()
    assert record["status"] == "op_failed"
    assert record["summary"]["parts_file"].endswith("program_parts.json")


def test_profile_dir_waits_for_the_device_and_writes_the_parts(
        monkeypatch, tmp_path):
    import dataclasses

    from agent_tpu.agent.app import Agent
    from agent_tpu.config import AgentConfig, Config

    log = []
    monkeypatch.setattr(jax.profiler, "trace",
                        lambda directory: FakeTrace(log, directory))
    config = Config(agent=AgentConfig(controller_url="http://test",
                                      tasks=("echo",)))
    config = dataclasses.replace(config, device=dataclasses.replace(
        config.device, profile_dir=str(tmp_path), profile_tasks=1))
    made = Agent(config=config, session=object(), runtime=FakeRuntime())
    made.profiled_call("echo", lambda: Result(log))
    assert log == ["profiler open", "result ready", "profiler closed"]
    assert os.path.exists(tmp_path / "plugins" / "profile" / "t0"
                          / "program_parts.json")
    # Past ``profile_tasks``: a plain call, no profiler, no wait.
    made.tasks_done = 1
    made.profiled_call("echo", lambda: Result(log))
    assert log[3:] == []
