"""The device runtime object: owns the mesh, the executable cache, and HBM-
resident model params.

Successor of reference ``ops/_tpu_runtime.py`` (the Edge-TPU interpreter
singleton): `get_tpu_handle(model_path)` becomes :meth:`TpuRuntime.get_params`
(weights live in HBM keyed by model id) + :meth:`TpuRuntime.run` (a cached
pjit-compiled executable instead of ``interpreter.invoke()``). Detection stays
proof-based like reference ``worker_sizing.py:203-213``: we claim only the
platform ``jax.devices()`` actually reports; env vars are hints, never proof.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Hashable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from agent_tpu.config import DeviceConfig
from agent_tpu.obs import trace as obs_trace
from agent_tpu.runtime.executor import (
    ExecutableCache,
    Program,
    install_xla_listener,
    parts_of_text,
)
from agent_tpu.runtime.mesh import build_mesh
from agent_tpu.utils.logging import log
from agent_tpu.utils.paths import cache_dir

# Every program this package's users jit is counted from here on, whichever
# entry point built it (a runtime, a bare ExecutableCache, a model's init).
install_xla_listener()


def parse_chip_slice(spec: str) -> Tuple[int, int]:
    """``"start:count"`` → ``(start, count)``, strictly validated.

    The slice grammar is deliberately tiny (two non-negative ints, count
    ≥ 1): a fleet launcher computes these, and a typo must fail the agent at
    boot — an agent silently running on the wrong chips would corrupt the
    whole fleet's placement arithmetic.
    """
    parts = spec.split(":")
    if len(parts) != 2:
        raise ValueError(
            f"CHIP_SLICE must be 'start:count', got {spec!r}"
        )
    try:
        start, count = int(parts[0]), int(parts[1])
    except ValueError as exc:
        raise ValueError(
            f"CHIP_SLICE must be 'start:count' ints, got {spec!r}"
        ) from exc
    if start < 0 or count < 1:
        raise ValueError(
            f"CHIP_SLICE needs start >= 0 and count >= 1, got {spec!r}"
        )
    return start, count


def apply_chip_slice(devices: Sequence, spec: str) -> list:
    """The ``[start, start+count)`` slice of ``devices`` — the device-pinning
    primitive of fleet mode (ISSUE 7). Out-of-range slices raise: truncating
    silently would run a 2-chip agent on 1 chip and skew every per-chip
    number derived from its leases."""
    start, count = parse_chip_slice(spec)
    if start + count > len(devices):
        raise ValueError(
            f"CHIP_SLICE {spec!r} wants devices [{start}, {start + count}) "
            f"but only {len(devices)} are visible"
        )
    return list(devices)[start:start + count]


def detect_platform(tpu_disabled: bool = False) -> str:
    """The platform we can *prove* we have: 'tpu' only if jax.devices() shows
    TPU devices (and the TPU_DISABLED kill-switch is off); else jax's default
    backend ('cpu'/'gpu'). Mirrors reference worker_sizing.py:195-213.

    With the kill-switch on we return 'cpu' *without* querying the default
    backend at all — ``jax.devices()`` would initialize the TPU backend (HBM
    prealloc, possible hang on a wedged chip), which is exactly what the
    switch exists to prevent.

    A backend that fails to initialize RAISES: an agent that was meant for a
    chip must not carry on on the CPU and report success from the wrong
    device.
    """
    if tpu_disabled:
        return "cpu"
    return jax.devices()[0].platform


class TpuRuntime:
    """One process-wide runtime: mesh + executable cache + HBM params store.

    Single-owner-of-the-device invariant (SURVEY.md §5.2): exactly one runtime
    owns the mesh; host threads stage data but never touch device state except
    through this object.
    """

    def __init__(
        self,
        config: Optional[DeviceConfig] = None,
        devices: Optional[Sequence[jax.Device]] = None,
    ) -> None:
        self.config = config or DeviceConfig()
        if not jax.config.jax_compilation_cache_dir:
            # Persistent XLA compile cache: restarts skip recompiles (§5.4).
            # JAX reads JAX_COMPILATION_CACHE_DIR itself; only when nothing
            # placed the cache from outside does it go to the checkout's one
            # fixed directory (the path is part of the cache key, so it must
            # not move between runs).
            jax.config.update(
                "jax_compilation_cache_dir", cache_dir("xla")
            )
        # Multi-host: join the coordination service BEFORE device discovery so
        # jax.devices() reports the global slice (SURVEY.md §5.8).
        from agent_tpu.runtime.distributed import maybe_initialize

        self.dist = maybe_initialize(
            self.config.coordinator_address,
            self.config.num_processes,
            self.config.process_id,
        )
        if devices is None:
            platform = detect_platform(self.config.tpu_disabled)
            devices = jax.devices(platform)
            if self.config.chip_slice:
                # Device-pinned fleet member (ISSUE 7): own only this
                # process's slice of the host's devices. Explicit `devices`
                # callers already chose, so the slice applies only to the
                # discovery path.
                devices = apply_chip_slice(devices, self.config.chip_slice)
        self.devices = list(devices)
        self.platform = self.devices[0].platform
        # Decided ONCE, from the devices this runtime owns: a TPU runtime
        # runs the Pallas kernels compiled (``interpret=False``, passed
        # explicitly at every selection site below) and nothing else selects
        # them — the kernels' own ``interpret=None`` auto-select is a test
        # convenience, never the serving decision.
        self.pallas = self.platform == "tpu" and self.config.pallas_attn
        if self.config.profile_port:
            # Live XProf endpoint (SURVEY.md §5.1): `xprof --port` /
            # TensorBoard can attach to capture device traces on demand.
            jax.profiler.start_server(self.config.profile_port)
        self.mesh: Mesh = build_mesh(self.devices, self.config.mesh_shape)
        self.cache = ExecutableCache()
        # Build-once dedup like executables, but not a program lookup: a
        # params build is timed on its own (runtime_params_seconds_total).
        self._params = ExecutableCache(count_lookups=False)
        self._model_ids: set = set()
        self._params_lock = threading.Lock()
        self._attention_fn = None
        self._train_attention_fn = None
        self._t5_kernel = None
        self._t5_kernel_built = False
        self.compute_dtype = self.config.compute_dtype

    # ---- topology ----

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def axis_size(self, name: str) -> int:
        return self.mesh.shape.get(name, 1)

    # ---- shardings ----

    def sharding(self, *spec: Any) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def data_sharding(self) -> NamedSharding:
        """Batch-dim-sharded over dp; trailing dims replicated."""
        return self.sharding("dp")

    def attention_fn(self):
        """The attention kernel for this mesh and platform.

        Selection (built once per runtime; kept out of the executable cache so
        its stats keep meaning "compiled programs"):

        - mesh has an ``sp`` axis > 1 → ring attention over ``sp``
          (``agent_tpu.parallel.ring``);
        - real TPU (and ``PALLAS_ATTN`` not disabled) → the fused Pallas
          kernels (``agent_tpu.kernels.flash_attention``): streaming from
          2048 keys, and the whole-row kernel the returned ``attn_fn``
          declares as ``attn_fn.whole_row`` for callers that can hand over
          [B, L, H*D] (``layers.attention``, ``bert.forward``);
        - otherwise → the dense XLA dot-product path.

        Each choice silently degrades to dense for unsupported shapes, so the
        returned callable is always a safe drop-in ``attn_fn``.
        """
        if self._attention_fn is None:
            if self.axis_size("sp") > 1:
                from agent_tpu.parallel.ring import make_ring_attention

                self._attention_fn = make_ring_attention(
                    self.mesh, use_flash_fold=self.pallas, interpret=False
                )
            elif self.pallas:
                from agent_tpu.kernels import make_flash_attention

                self._attention_fn = make_flash_attention(
                    self.mesh, interpret=False
                )
            else:
                from agent_tpu.models.layers import dot_product_attention

                self._attention_fn = dot_product_attention
        return self._attention_fn

    def train_attention_fn(self):
        """The DIFFERENTIABLE attention kernel for the training path.

        Same platform gate as :meth:`attention_fn`, but selects
        ``kernels.make_flash_attention_trainable`` — the ``custom_vjp``
        variant whose backward is also a Pallas kernel — instead of the
        forward-only inference kernel (which autodiff cannot trace through).
        Ring attention (``sp`` > 1) is forward-only today, so sp meshes train
        on the dense path; both flash and dense degrade to dense for
        unsupported shapes, keeping the return a safe drop-in ``attn_fn``.
        """
        if self._train_attention_fn is None:
            if self.pallas and self.axis_size("sp") == 1:
                from agent_tpu.kernels import make_flash_attention_trainable

                self._train_attention_fn = make_flash_attention_trainable(
                    self.mesh, interpret=False
                )
            else:
                from agent_tpu.models.layers import dot_product_attention

                self._train_attention_fn = dot_product_attention
        return self._train_attention_fn

    def t5_attention_kernel(self):
        """The fused T5 bias-attention kernel for this mesh, or ``None``.

        T5's encoder self-attention carries a bucketed relative-position
        bias, so it cannot ride the generic :meth:`attention_fn`; it has its
        own Pallas kernel (``kernels.flash_attention_t5``, bias computed per
        tile in VMEM) and mesh wrapper (``make_flash_attention_t5`` — batch
        over dp, heads over tp). Same platform gate as the generic kernel.
        ``None`` means "dense path" (``t5.encode`` builds the dense bias
        lazily); the kernel itself also declines unsupported shapes at
        trace time, ticking the ``t5_dense`` selection counter.
        """
        if not self._t5_kernel_built:
            self._t5_kernel_built = True
            if self.pallas:
                from agent_tpu.kernels.flash_attention import (
                    make_flash_attention_t5,
                )

                self._t5_kernel = make_flash_attention_t5(
                    self.mesh, interpret=False
                )
        return self._t5_kernel

    def replicated(self) -> NamedSharding:
        return self.sharding()

    # ---- params store (TPUHandle cache generalized) ----

    def get_params(
        self,
        model_id: str,
        build: Callable[[], Any],
        specs: Any = None,
    ) -> Any:
        """Weights resident on device, built once per process per model id.

        ``build()`` returns a pytree. Leaves that are already device-committed
        ``jax.Array``\\ s (a model that sharded its own params over tp) are left
        exactly as built; host leaves (numpy) are placed on the mesh —
        **sharded** per ``specs`` (a PartitionSpec pytree, e.g.
        ``parallel.shardings.encoder_param_specs``) when the mesh has a
        model-parallel axis > 1, replicated otherwise. This is how the serving
        path runs models that exceed one chip's HBM (SURVEY.md §2.8 TP row):
        the op passes its spec tree and XLA inserts the tp collectives in the
        forward. Leaves whose dims don't divide the mesh replicate (see
        ``shardings.sanitize_specs``). Build-once dedup rides the same
        per-key-event cache as executables, so concurrent first callers
        trigger exactly one build / one HBM transfer.
        """
        # Any model-parallel axis (tp for dense Megatron sharding, ep for
        # MoE expert sharding) activates spec placement; sanitize_specs
        # strips axes the mesh doesn't carry.
        use_specs = specs is not None and (
            self.axis_size("tp") > 1 or self.axis_size("ep") > 1
        )

        def put_tree() -> Any:
            host = build()
            if not use_specs:
                return jax.tree_util.tree_map(
                    lambda leaf: leaf
                    if isinstance(leaf, jax.Array) and leaf.committed
                    else jax.device_put(leaf, self.replicated()),
                    host,
                )
            from agent_tpu.parallel.shardings import sanitize_specs

            safe = sanitize_specs(self.mesh, host, specs)

            def put(leaf, spec):
                if isinstance(leaf, jax.Array) and leaf.committed:
                    return leaf
                return jax.device_put(
                    leaf, NamedSharding(self.mesh, spec))

            return jax.tree_util.tree_map(
                put, host, safe, is_leaf=lambda x: isinstance(x, P)
            )

        def place() -> Any:
            t0 = time.perf_counter()
            try:
                # ``device_put`` only enqueues: wait for the weights to
                # land, or the counter holds the host build alone and the
                # transfer hides in whatever first touches them. Once a
                # model; the program that needs them waits for them anyway.
                return jax.block_until_ready(put_tree())
            finally:
                # In the calling task's registry, as the compile listener
                # counts: the first task of a model pays for its weights.
                obs_trace.record_params_build(time.perf_counter() - t0)

        with self._params_lock:
            self._model_ids.add(model_id)
        # Placement mode is part of the identity: the same model id requested
        # replicated and tp-sharded must not alias one cache entry.
        key = ("params", model_id, "tp" if use_specs else "rep")
        return self._params.get_or_build(key, place)

    def evict_params(self, model_id: str) -> None:
        with self._params_lock:
            self._model_ids.discard(model_id)
        # Both placement modes: the id may be resident sharded or replicated.
        self._params.evict(("params", model_id, "tp"))
        self._params.evict(("params", model_id, "rep"))

    def clear_params(self) -> None:
        """Drop EVERY resident model from the HBM params store.

        The store is append-only by design (serving re-uses hot weights),
        so a workload that cycles through many large one-off models — the
        bench's 8-expert MoE tree is ~2 GB — must be able to give the HBM
        back: without this, the r4 bench's later train legs hit
        RESOURCE_EXHAUSTED on a 16 GB chip. Freeing is by reference drop;
        the next ``get_params`` for any id simply re-transfers.
        """
        with self._params_lock:
            self._model_ids.clear()
        self._params.clear()

    # ---- compiled execution ----

    def compiled(
        self,
        key: Tuple[Hashable, ...],
        build: Callable[[], Callable],
    ) -> Callable:
        """Executable for ``key``, compiling at most once (see ExecutableCache).
        Until its first call is over the caller gets the first-call layer
        (``executor.Program``), after it the ``jax.jit`` wrapper itself."""
        program = self.cache.get_or_build(key, lambda: Program(build()))
        return program if program.noted is None else program.wrapper

    def program_parts(self) -> Dict[str, list]:
        """ON DEMAND (a traced benchmark run after its window, an operator's
        capture): which part of a model (``obs.trace.PARTS``) every
        instruction of every program that has run belongs to, read out of
        the compiled text: ``{module name: [{"instructions": {name: part or
        None}, "mixed": {fusion: [parts]}, "named_share": share}, ...]}``,
        one map an executable (two shapes of one function share a module
        name; a trace's reader takes the map that holds the instruction
        names it sees). Asking a program that has run for its text is a
        lookup, not a compile (``executor.Program.compiled_text``). Never
        called on the hot path or in an untraced run."""
        out: Dict[str, list] = {}
        for program in self.cache.values():
            text = program.compiled_text()
            if text is not None:
                module, parts = parts_of_text(text)
                out.setdefault(module, []).append(parts)
        return out

    def _model_ids_snapshot(self) -> set:
        with self._params_lock:
            return set(self._model_ids)

    def put_batch(self, arr: np.ndarray) -> jax.Array:
        """Host batch → device, batch dim sharded over dp.

        The batch dim must divide the dp axis — callers pad with
        ``pad_batch(batch_buckets=...)`` so this holds by construction.
        """
        return jax.device_put(arr, self.data_sharding())

    def peak_flops(self) -> Optional[float]:
        """Peak dense-bf16 FLOP/s of one device (MFU denominator, ISSUE 8):
        the ``PEAK_TFLOPS`` env override first, else the public spec-sheet
        table keyed by device_kind; None when unknown — MFU is then simply
        not exported, never guessed."""
        from agent_tpu.obs.health import resolve_peak_flops

        return resolve_peak_flops(self)

    def describe(self) -> Dict[str, Any]:
        """Telemetry snapshot for the lease metrics channel (SURVEY.md §5.5)."""
        out: Dict[str, Any] = {
            "platform": self.platform,
            "n_devices": self.n_devices,
            "mesh": dict(self.mesh.shape),
            "compute_dtype": self.compute_dtype,
            # Fleet-default quantized execution mode (TPU_QUANT via
            # DeviceConfig.quant): operators can see from lease telemetry
            # whether a worker serves int8/w8a16 by default. Per-task
            # resolution stays in ops/_model_common.apply_quant_env.
            "quant_default": self.config.quant or "none",
            "executable_cache": self.cache.stats(),
            "models_resident": sorted(self._model_ids_snapshot()),
        }
        if self.config.chip_slice:
            # Fleet mode (ISSUE 7): which slice of the host this runtime
            # owns — rides the lease telemetry so the controller's fleet
            # view can attribute chips per agent.
            out["chip_slice"] = self.config.chip_slice
        # HBM telemetry across ALL owned devices (ISSUE 9 satellite — the
        # old probe read only devices[0], so a CHIP_SLICE fleet member or
        # dp=N mesh agent attributed memory for one chip out of N). The
        # legacy keys become fleet-correct TOTALS; the per-device breakdown
        # rides alongside. Absent entirely on backends without stats (CPU).
        from agent_tpu.obs.profile import hbm_totals

        try:
            hbm = hbm_totals(self.devices)
        except Exception:  # noqa: BLE001 — telemetry must never raise
            hbm = None
        if hbm:
            if "used" in hbm:
                out["hbm_bytes_in_use"] = hbm["used"]
            if "limit" in hbm:
                out["hbm_bytes_limit"] = hbm["limit"]
            if "peak" in hbm:
                out["hbm_peak_bytes"] = hbm["peak"]
            out["hbm_per_device"] = hbm["per_device"]
        return out


# Process-wide singleton, lazily built (reference _tpu_runtime.py:34-43 pattern).
_runtime: Optional[TpuRuntime] = None
_runtime_lock = threading.Lock()


def get_runtime(config: Optional[DeviceConfig] = None) -> TpuRuntime:
    global _runtime
    with _runtime_lock:
        if _runtime is None:
            _runtime = TpuRuntime(config)
            log(
                "runtime up",
                platform=_runtime.platform,
                devices=_runtime.n_devices,
                mesh=dict(_runtime.mesh.shape),
            )
        return _runtime


def reset_runtime() -> None:
    """Tests only: drop the singleton so the next get_runtime rebuilds."""
    global _runtime
    with _runtime_lock:
        _runtime = None
