"""Multi-chip fleet machinery (ISSUE 7): chip-slice pinning (config →
runtime → capabilities), the fleet launcher's per-member environments, and
the MPMD summarize encode/decode pipeline split (dep-gated across two
agents, bit-identical to the monolithic op)."""

import json

import jax
import pytest

from agent_tpu.agent import fleet
from agent_tpu.agent.app import Agent
from agent_tpu.chaos import LoopbackSession
from agent_tpu.config import AgentConfig, Config, DeviceConfig
from agent_tpu.controller.core import Controller
from agent_tpu.runtime.runtime import (
    TpuRuntime,
    apply_chip_slice,
    parse_chip_slice,
)

TINY_S2S = {
    "d_model": 32, "n_heads": 4, "n_enc_layers": 1, "n_dec_layers": 1,
    "d_ff": 64, "max_src_len": 64, "max_tgt_len": 16, "dtype": "float32",
}


@pytest.fixture(scope="module")
def runtime():
    return TpuRuntime(
        config=DeviceConfig(tpu_disabled=True, mesh_shape={"dp": 8}),
        devices=jax.devices("cpu"),
    )


# ---- chip-slice grammar + runtime pinning ----

class TestChipSlice:
    def test_parse_valid(self):
        assert parse_chip_slice("0:1") == (0, 1)
        assert parse_chip_slice("4:2") == (4, 2)

    @pytest.mark.parametrize(
        "bad", ["", "3", "1:2:3", "a:1", "1:b", "-1:2", "0:0", "0:-1"]
    )
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            parse_chip_slice(bad)

    def test_apply_slices_and_bounds(self):
        devices = list(range(8))  # any sequence works
        assert apply_chip_slice(devices, "0:2") == [0, 1]
        assert apply_chip_slice(devices, "6:2") == [6, 7]
        with pytest.raises(ValueError):
            apply_chip_slice(devices, "7:2")  # out of range, not truncated

    def test_config_reads_chip_slice_env(self, monkeypatch):
        monkeypatch.setenv("CHIP_SLICE", "2:2")
        assert DeviceConfig.from_env().chip_slice == "2:2"
        monkeypatch.delenv("CHIP_SLICE")
        assert DeviceConfig.from_env().chip_slice == ""

    def test_runtime_owns_only_its_slice(self):
        # conftest forces 8 virtual CPU devices, so a real subset exists.
        rt = TpuRuntime(
            config=DeviceConfig(tpu_disabled=True, chip_slice="2:2")
        )
        assert rt.n_devices == 2
        assert rt.devices == jax.devices("cpu")[2:4]
        assert rt.describe()["chip_slice"] == "2:2"
        assert dict(rt.mesh.shape)["dp"] == 2  # dp absorbs the slice

    def test_explicit_devices_ignore_slice(self):
        # Callers that hand devices in already chose; the slice is for the
        # discovery path only.
        rt = TpuRuntime(
            config=DeviceConfig(tpu_disabled=True, chip_slice="0:1"),
            devices=jax.devices("cpu"),
        )
        assert rt.n_devices == len(jax.devices("cpu"))

    def test_agent_capabilities_advertise_slice(self):
        cfg = Config(
            agent=AgentConfig(tasks=("echo",)),
            device=DeviceConfig(chip_slice="1:3"),
        )
        agent = Agent(config=cfg, session=object())
        assert agent.capabilities()["chip_slice"] == "1:3"
        plain = Agent(
            config=Config(agent=AgentConfig(tasks=("echo",))),
            session=object(),
        )
        assert "chip_slice" not in plain.capabilities()


# ---- launcher environment computation ----

class TestFleetEnv:
    def test_cpu_members_get_disjoint_slices_and_forced_devices(self):
        envs = [
            fleet.agent_env(
                i, 2, 2, controller_url="http://c:1", tasks="echo",
                platform="cpu", base_env={"XLA_FLAGS": "--keep=1 "
                "--xla_force_host_platform_device_count=8"},
            )
            for i in range(2)
        ]
        assert [e["CHIP_SLICE"] for e in envs] == ["0:2", "2:2"]
        assert [e["AGENT_NAME"] for e in envs] == ["fleet-0", "fleet-1"]
        for e in envs:
            # Inherited forced count REPLACED with the fleet's total.
            assert "--xla_force_host_platform_device_count=4" in \
                e["XLA_FLAGS"]
            assert "device_count=8" not in e["XLA_FLAGS"]
            assert "--keep=1" in e["XLA_FLAGS"]
            assert e["JAX_PLATFORMS"] == "cpu"
            assert e["CONTROLLER_URL"] == "http://c:1"
            assert e["TASKS"] == "echo"

    def test_tpu_members_pin_at_process_level(self):
        env = fleet.agent_env(
            0, 1, 4, controller_url="http://c:1", tasks="echo",
            platform="tpu", base_env={"JAX_PLATFORMS": "cpu"},
            mesh_shape="dp=4",
        )
        assert env["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
        # In-process slice is identity over the restricted view.
        assert env["CHIP_SLICE"] == "0:4"
        assert "XLA_FLAGS" not in env or \
            "force_host_platform" not in env["XLA_FLAGS"]
        # A child that cannot reach its chip fails; it never inherits the
        # parent's CPU pin.
        assert env["JAX_PLATFORMS"] == "tpu"

    def test_tpu_members_are_whole_slices_on_their_own_ports(self):
        """Four one-chip libtpu processes on one host: each sees one chip,
        is a slice by itself, and owns a distinct runtime port."""
        envs = [
            fleet.agent_env(
                i, 4, 1, controller_url="http://c:1", tasks="echo",
                platform="tpu", base_env={},
            )
            for i in range(4)
        ]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        for e in envs:
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert e["CLOUD_TPU_TASK_ID"] == "0"
            assert e["TPU_PROCESS_ADDRESSES"] == \
                f"localhost:{e['TPU_PROCESS_PORT']}"
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4

    @pytest.mark.parametrize("chips", [2, 3, 8])
    def test_tpu_chip_count_never_run_raises(self, chips):
        """Only the per-process chip counts that have run on hardware have
        bounds; any other is an error here, not a hang on the host."""
        with pytest.raises(ValueError, match="chip bounds"):
            fleet.agent_env(
                0, 1, chips, controller_url="http://c:1", tasks="echo",
                platform="tpu", base_env={},
            )

    def test_mesh_and_warm_ride_through(self):
        env = fleet.agent_env(
            0, 1, 4, controller_url="http://c:1", tasks="echo",
            platform="cpu", base_env={}, mesh_shape="dp=4",
            warm_file="/tmp/w.json", extra_env={"IDLE_SLEEP_SEC": "0.01"},
        )
        assert env["MESH_SHAPE"] == "dp=4"
        assert env["AGENT_WARM_FILE"] == "/tmp/w.json"
        assert env["IDLE_SLEEP_SEC"] == "0.01"

    def test_index_bounds(self):
        with pytest.raises(ValueError):
            fleet.agent_env(
                2, 2, 1, controller_url="u", tasks="t", base_env={}
            )

    def test_force_host_devices_idempotent(self):
        flags = fleet.force_host_devices("", 4)
        assert flags == "--xla_force_host_platform_device_count=4"
        assert fleet.force_host_devices(flags, 2) == \
            "--xla_force_host_platform_device_count=2"


# ---- MPMD pipeline split (stretch): encode/decode across two agents ----

class TestMpmdPipeline:
    def _stage_agent(self, controller, runtime, name, tasks):
        agent = Agent(
            config=Config(agent=AgentConfig(
                controller_url="http://loopback", agent_name=name,
                tasks=tasks, idle_sleep_sec=0.0,
            )),
            session=LoopbackSession(controller), runtime=runtime,
        )
        agent._profile = {"tier": "test"}
        return agent

    def test_chained_stages_equal_monolithic(self, runtime):
        from agent_tpu.ops import get_op
        from agent_tpu.runtime.context import OpContext

        texts = [f"mpmd row {i} with text to summarize" for i in range(24)]
        mono = get_op("map_summarize")(
            {"texts": texts, "max_length": 6, "model_config": dict(TINY_S2S)},
            OpContext(runtime=runtime),
        )
        assert mono["ok"] is True

        controller = Controller()
        enc_id = controller.submit(
            "summarize_encode",
            {"texts": texts, "model_config": dict(TINY_S2S)},
        )
        dec_id = controller.submit(
            "summarize_decode",
            {"max_length": 6, "model_config": dict(TINY_S2S),
             "__collect_partials__": True},
            after=[enc_id],
        )
        enc_agent = self._stage_agent(
            controller, runtime, "enc", ("summarize_encode",))
        dec_agent = self._stage_agent(
            controller, runtime, "dec", ("summarize_decode",))
        # Dep gating: the decode stage cannot lease before encode posts.
        assert dec_agent.step() is False
        for _ in range(20):
            if controller.drained():
                break
            enc_agent.step()
            dec_agent.step()
        assert controller.drained(), controller.counts()
        assert controller.job_snapshot(enc_id)["agent"] == "enc"
        dec_snap = controller.job_snapshot(dec_id)
        assert dec_snap["agent"] == "dec"
        assert dec_snap["result"]["summaries"] == mono["summaries"]

    def test_encode_result_survives_json_round_trip(self, runtime):
        """The inter-stage wire is a result body: a JSON round trip (what
        the controller journal/HTTP do) must not perturb the activations
        the decode stage resumes from."""
        from agent_tpu.ops import get_op
        from agent_tpu.runtime.context import OpContext

        texts = ["round trip row one", "round trip row two"]
        ctx = OpContext(runtime=runtime)
        enc = get_op("summarize_encode")(
            {"texts": texts, "model_config": dict(TINY_S2S)}, ctx)
        assert enc["ok"] is True and enc["n_rows"] == 2
        dec_direct = get_op("summarize_decode")(
            {"encoded": enc, "max_length": 6,
             "model_config": dict(TINY_S2S)}, ctx)
        dec_rt = get_op("summarize_decode")(
            {"encoded": json.loads(json.dumps(enc)), "max_length": 6,
             "model_config": dict(TINY_S2S)}, ctx)
        assert dec_direct["summaries"] == dec_rt["summaries"]
        assert len(dec_rt["summaries"]) == 2

    def test_decode_rejects_malformed_inputs(self, runtime):
        from agent_tpu.ops import get_op
        from agent_tpu.runtime.context import OpContext

        ctx = OpContext(runtime=runtime)
        out = get_op("summarize_decode")({"max_length": 6}, ctx)
        assert out["ok"] is False
        out = get_op("summarize_decode")(
            {"encoded": {"op": "other"}, "max_length": 6}, ctx)
        assert out["ok"] is False

    def test_empty_rows_stay_blank_through_the_chain(self, runtime, tmp_path):
        """Drain-mode blank cells: the encode stage marks them, the decode
        stage blanks them — same contract as the fused op."""
        from agent_tpu.ops import get_op
        from agent_tpu.runtime.context import OpContext

        csv = tmp_path / "rows.csv"
        csv.write_text(
            'id,text\n0,"first row"\n1,""\n2,"third row"\n',
            encoding="utf-8",
        )
        ctx = OpContext(runtime=runtime)
        enc = get_op("summarize_encode")(
            {"source_uri": str(csv), "start_row": 0, "shard_size": 3,
             "text_field": "text", "model_config": dict(TINY_S2S)}, ctx)
        assert enc["ok"] is True and enc["empty_rows"] == [1]
        dec = get_op("summarize_decode")(
            {"encoded": enc, "max_length": 6,
             "model_config": dict(TINY_S2S)}, ctx)
        assert dec["summaries"][1] == ""
        assert dec["summaries"][0] != ""


class TestWaitForAgents:
    """ISSUE 10 satellite: the readiness gate's timeout and
    partial-readiness paths (only the happy path was covered)."""

    def _agents_fn(self, *snapshots):
        """agents_fn returning successive snapshots, then the last forever."""
        seq = list(snapshots)

        def fn():
            return seq.pop(0) if len(seq) > 1 else seq[0]

        return fn

    def test_all_ready_immediately(self):
        from agent_tpu.agent.fleet import wait_for_agents

        fn = self._agents_fn({"a": {}, "b": {}})
        assert wait_for_agents(fn, ["a", "b"], timeout=1.0) is True

    def test_partial_readiness_converges(self):
        from agent_tpu.agent.fleet import wait_for_agents

        fn = self._agents_fn({}, {"a": {}}, {"a": {}, "b": {}})
        assert wait_for_agents(fn, ["a", "b"], timeout=5.0) is True

    def test_partial_readiness_times_out(self):
        import time

        from agent_tpu.agent.fleet import wait_for_agents

        t0 = time.monotonic()
        fn = self._agents_fn({"a": {}})  # b never reports in
        assert wait_for_agents(fn, ["a", "b"], timeout=0.4) is False
        assert time.monotonic() - t0 >= 0.3  # actually waited the window

    def test_agents_fn_errors_tolerated_until_timeout(self):
        from agent_tpu.agent.fleet import wait_for_agents

        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise ConnectionError("controller still booting")
            return {"a": {}}

        assert wait_for_agents(flaky, ["a"], timeout=5.0) is True
        assert calls["n"] >= 3

    def test_dead_member_aborts_the_wait(self):
        from agent_tpu.agent.fleet import Fleet, wait_for_agents

        class DeadProc:
            returncode = 3

            def poll(self):
                return 3

        fleet = Fleet([DeadProc()], ["a"])
        # b never reports AND a member already exited nonzero: fail fast,
        # not at the timeout.
        import time

        t0 = time.monotonic()
        ok = wait_for_agents(
            self._agents_fn({}), ["a"], timeout=30.0, fleet=fleet
        )
        assert ok is False
        assert time.monotonic() - t0 < 5.0
