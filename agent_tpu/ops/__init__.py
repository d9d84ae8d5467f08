"""Op registry and dispatch.

This is the *intended* design of the reference registry (reference
``ops/__init__.py:35-108`` + ``ops_loader.py``) with its four shipped wiring gaps
fixed (SURVEY.md §1):

1. The registry is the **only** dispatch table — the agent loop uses it (the
   reference agent ignored its registry and kept a private 2-entry dict,
   reference ``app.py:135-138``).
2. Every entry in ``OP_TO_MODULE`` maps to a module that exists (the reference
   mapped four phantom modules, reference ``ops/__init__.py:21-25``).
3. Registered names equal map keys (the reference registered ``read_csv_shard``
   under map key ``csv_shard``, making the op unreachable both ways,
   reference ``ops/__init__.py:20`` vs ``ops/csv_shard.py:29``).
4. The ERP triggers are proper registered ops (the reference shipped them as
   bare unwired ``run()`` functions, reference ``ops/trigger_sap.py:9``).

Semantics preserved from the reference:
- ``register_op(name)`` decorator populates the registry at module import
  (ref ``ops/__init__.py:35-39``).
- Lazy import: modules load on first ``get_op``; import failures are recorded in
  ``OPS_LOAD_ERRORS`` and surfaced in rich error messages, never at package
  import (ref ``ops/__init__.py:74-84``), so the agent boots on hosts missing
  heavy deps — the moral equivalent of booting without pycoral
  (ref ``ops/_tpu_runtime.py:45-46``).
- TASKS gating with ``*``/``all``/``none`` sentinels (ref ``ops/__init__.py:42-71``).

Op call contract: ``fn(payload: dict, ctx: OpContext | None = None) -> dict``.
The optional ``ctx`` carries the device runtime (mesh, compiled-op cache); pure
host ops ignore it — same shape as the reference's optional ``ctx`` on the TPU op
(ref ``ops/map_classify_tpu.py:32``).
"""

from __future__ import annotations

import importlib
import os
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

OpFn = Callable[..., Dict[str, Any]]

# name -> handler. Populated by @register_op side effects at module import.
OPS_REGISTRY: Dict[str, OpFn] = {}
# [(module_name, repr(error))] — import failures, recorded not raised.
OPS_LOAD_ERRORS: List[Tuple[str, str]] = []

# Static lazy-import map: op name -> submodule of agent_tpu.ops.
# Invariant (tested): every module exists and registers exactly its key.
OP_TO_MODULE: Dict[str, str] = {
    "echo": "echo",
    "map_tokenize": "map_tokenize",
    "map_classify_tpu": "map_classify_tpu",
    "map_summarize": "map_summarize",
    # MPMD pipeline stages (ISSUE 7 stretch): summarize's encoder and
    # decoder as separate ops, chained across agents via dep-gating.
    "summarize_encode": "summarize_mpmd",
    "summarize_decode": "summarize_mpmd",
    # Request-serving ops (ISSUE 15): the agent half of POST /v1/infer —
    # batched interactive classify + the continuous-batching decode engine.
    "serve_classify": "serve_infer",
    "serve_summarize": "serve_infer",
    # Disaggregated serving pools (ISSUE 16): prefill and decode as
    # separate ops so the fleets can split (SERVE_DISAGG=1), chained via
    # dep-gating like the MPMD stages.
    "serve_prefill": "serve_infer",
    "serve_decode": "serve_infer",
    "read_csv_shard": "csv_shard",       # name == registered name (gap 3 fixed)
    "risk_accumulate": "risk_accumulate",
    "trigger_sap": "trigger_sap",        # now a real registered op (gap 4 fixed)
    "trigger_oracle": "trigger_oracle",
    "train_classifier": "train_classifier",  # train → .npz artifact → serve
    # Bulk scoring of pre-tokenized documents with a decoder language model
    # (ISSUE 27): a row longer than one program, state handed on on device.
    "map_score_lm": "map_score_lm",
}

# Deterministic ops whose results may be served from the content-addressed
# result cache (ISSUE 19): same payload + model version => bit-identical
# result dict. Excluded on purpose: ``read_csv_shard`` (reads mutable files
# behind a URI), the ERP triggers (external side effects), ``train_classifier``
# (writes an artifact), and the decode-side serving ops (their payloads embed
# per-request ids). The serving front door caches ``serve_classify`` /
# ``serve_summarize`` at request granularity itself, keyed on
# (op, text, params) before bucketing.
CACHEABLE_OPS = frozenset(
    {
        "echo",
        "map_tokenize",
        "map_classify_tpu",
        "map_summarize",
        "summarize_encode",
        "summarize_decode",
        "risk_accumulate",
    }
)


def is_cacheable(name: str) -> bool:
    """True when ``name`` is registered as deterministic/cache-safe."""
    return name in CACHEABLE_OPS


_imported: Dict[str, bool] = {}
_lock = threading.Lock()
_plugins_loaded = False


def load_plugins(paths: Optional[str] = None) -> List[str]:
    """Load extra op modules from ``OPS_PLUGIN_PATH`` (``:``-separated files).

    The reference's extension point was an optional ``tpu_ops.py`` imported
    from beside the app (reference ``app.py:118-123``) that could provide
    ``map_classify_tpu``. Generalized: each path is executed as a module and
    its ``@register_op`` decorations land in the shared registry (and in
    ``OP_TO_MODULE`` so TASKS gating and ``list_ops`` see them). Missing files
    and import errors are recorded in ``OPS_LOAD_ERRORS``, never raised — the
    agent must boot without its plugins, like the reference without
    ``tpu_ops.py`` (ref ``app.py:126-132``).

    Returns the op names newly registered by plugins.
    """
    global _plugins_loaded
    raw = paths if paths is not None else os.environ.get("OPS_PLUGIN_PATH", "")
    if paths is None:
        with _lock:
            if _plugins_loaded:
                return []
            _plugins_loaded = True
    new_names: List[str] = []
    for path in [p for p in (raw or "").split(":") if p.strip()]:
        before = set(OPS_REGISTRY)
        try:
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                f"agent_tpu_plugin_{abs(hash(path)) & 0xFFFF:04x}", path
            )
            if spec is None or spec.loader is None:
                raise ImportError(f"cannot load plugin {path!r}")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        except Exception as exc:  # noqa: BLE001 — recorded, not raised
            OPS_LOAD_ERRORS.append((f"plugin:{path}", repr(exc)))
            # Roll back partial registrations: an op registered by a plugin
            # that then failed to import would otherwise sit in OPS_REGISTRY
            # with no OP_TO_MODULE entry — registered but unreachable.
            for name in set(OPS_REGISTRY) - before:
                if name not in OP_TO_MODULE:
                    del OPS_REGISTRY[name]
            continue
        for name in set(OPS_REGISTRY) - before:
            if name in OP_TO_MODULE:
                # A builtin registered as a side effect of the plugin's own
                # imports (e.g. `from agent_tpu.ops.echo import run`) — not
                # the plugin's op; leave its builtin attribution alone.
                continue
            OP_TO_MODULE[name] = f"plugin:{path}"
            _imported[f"plugin:{path}"] = True
            new_names.append(name)
    return new_names


def register_op(name: str) -> Callable[[OpFn], OpFn]:
    """Decorator: register ``fn`` under ``name`` (ref ops/__init__.py:35-39)."""

    def deco(fn: OpFn) -> OpFn:
        OPS_REGISTRY[name] = fn
        return fn

    return deco


def _parse_tasks_env(raw: Optional[str] = None) -> Optional[List[str]]:
    """TASKS env → enabled-op filter. None means "no filter" (all enabled).

    Sentinels per reference ``ops/__init__.py:42-57``: ``*`` or ``all`` → all ops;
    ``none`` → empty set; unset → all.
    """
    if raw is None:
        raw = os.environ.get("TASKS", "")
    toks = [t.strip() for t in raw.split(",") if t.strip()]
    if not toks:
        return None
    low = [t.lower() for t in toks]
    if "*" in toks or "all" in low:
        return None
    if low == ["none"]:
        return []
    return toks


def _is_enabled(name: str, tasks: Optional[List[str]] = None) -> bool:
    enabled = _parse_tasks_env() if tasks is None else (_parse_tasks_env(",".join(tasks)) if tasks else [])
    return enabled is None or name in enabled


def list_ops() -> List[str]:
    """All known op names, filtered by the TASKS gate (ref ops/__init__.py:60-65)."""
    enabled = _parse_tasks_env()
    names = sorted(OP_TO_MODULE)
    if enabled is None:
        return names
    return [n for n in names if n in enabled]


def _import_op_module(module: str) -> None:
    """Import ``agent_tpu.ops.<module>`` once; record failures (ref :74-84)."""
    with _lock:
        if _imported.get(module):
            return
        try:
            importlib.import_module(f"agent_tpu.ops.{module}")
            _imported[module] = True
        except Exception as exc:  # noqa: BLE001 — deliberately broad, recorded
            OPS_LOAD_ERRORS.append((module, repr(exc)))
            _imported[module] = False


def get_op(name: str) -> OpFn:
    """Resolve an op name to its handler, or raise with a rich diagnostic.

    Resolution order mirrors reference ``ops/__init__.py:87-108``:
    enabled-check → module map → lazy import → registry lookup.
    """
    if not _is_enabled(name):
        raise KeyError(
            f"op {name!r} is not enabled by TASKS={os.environ.get('TASKS', '')!r}; "
            f"enabled ops: {list_ops()}"
        )
    module = OP_TO_MODULE.get(name)
    if module is None:
        raise KeyError(
            f"unknown op {name!r}; known ops: {sorted(OP_TO_MODULE)}"
        )
    _import_op_module(module)
    fn = OPS_REGISTRY.get(name)
    if fn is None:
        errs = "; ".join(f"{m}: {e}" for m, e in OPS_LOAD_ERRORS[:10])
        raise KeyError(
            f"op {name!r} did not register (module {module!r}). "
            f"registered: {sorted(OPS_REGISTRY)}. import errors: {errs or 'none'}"
        )
    return fn


def load_ops(tasks: List[str]) -> Dict[str, OpFn]:
    """Resolve a list of op names at startup; raise early on any unknown/disabled
    name (successor of reference ``ops_loader.py:8-19`` — now actually used by
    the agent)."""
    load_plugins()  # OPS_PLUGIN_PATH extras join the registry first (once)
    handlers: Dict[str, OpFn] = {}
    for name in tasks:
        handlers[name] = get_op(name)
    return handlers
