"""Versioned ahead-of-time export artifacts (ROADMAP item 3).

One artifact is a DIRECTORY holding a ``manifest.json`` plus one
serialized StableHLO module per captured topology (and, for block
captures, the parameter values) — the NNVM-``export``/`SymbolBlock`
capability mapped onto `jax.export` (SURVEY §7 stage 3):

.. code-block:: text

    <path>/
      manifest.json                  format_version, kind, topology table,
                                     remat policy, autotune configs, hashes
      module_<mkey>.stablehlo        jax.export blob per topology (and per
                                     chunk width for serve_step artifacts)
      params.npz                     block captures only: parameter values

The manifest records everything a FRESH process needs to run the
program without re-tracing any model Python: flattened input avals,
batch sharding specs, the mesh ``topology()`` in effect, the autotune
``BlockConfig``\\ s the capture traced with, and the remat policy the
offline search picked.  ``hash`` (sha256 over the module bytes) keys
the persistent compile cache next door: XLA keys executables by HLO, so
two replicas loading the same artifact compile once per cluster.

Failure matrix (docs/export.md): a manifest whose ``format_version``
this build doesn't speak, a module captured for a different device
count/axes, or avals that no longer match all raise `MXNetError` at
load time with the mismatch spelled out — never a silent retrace.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence

from ..base import MXNetError

__all__ = ["FORMAT_VERSION", "export_dir", "topology_key", "ExportArtifact"]

# bump when the manifest schema changes incompatibly; load() refuses
# versions it doesn't speak (stale-version row of the failure matrix)
FORMAT_VERSION = 1

_MANIFEST = "manifest.json"
_PARAMS = "params.npz"


def export_dir() -> Optional[str]:
    """Resolve the artifact store: ``MXTPU_EXPORT_DIR``, else an
    ``export/`` subdirectory of the active compile cache
    (`runtime.compile_cache_dir` — artifacts live next to the compiled
    binaries they warm), else None."""
    d = os.environ.get("MXTPU_EXPORT_DIR")
    if d:
        return d
    from ..runtime import compile_cache_dir
    cc = compile_cache_dir()
    return os.path.join(cc, "export") if cc else None


def auto_capture_enabled() -> bool:
    """``MXTPU_EXPORT=1``: warmup paths capture+save after a live
    compile and load a matching artifact instead of tracing."""
    from ..base import getenv_bool
    return getenv_bool("MXTPU_EXPORT", False)


def topology_key(topology: Dict[str, Any], tag: str = "") -> str:
    """Stable key for one captured module: device count + named axis
    sizes (+ an optional tag, e.g. the serve chunk width)."""
    axes = topology.get("axes") or {}
    ax = "x".join(f"{k}{int(v)}" for k, v in sorted(axes.items()))
    key = f"d{int(topology.get('devices', 1))}_{ax or 'none'}"
    return f"{key}_{tag}" if tag else key


def _aval_list(avals) -> List[List[Any]]:
    """Flatten a pytree of avals/arrays to [[shape, dtype], ...]."""
    import jax
    leaves = jax.tree_util.tree_leaves(avals)
    return [[list(getattr(a, "shape", ())),
             str(getattr(a, "dtype", type(a).__name__))] for a in leaves]


def _aval_mismatch(stored: List[List[Any]], current) -> Optional[str]:
    """First difference between a stored aval list and a live tree."""
    cur = _aval_list(current)
    if len(stored) != len(cur):
        return (f"input tree has {len(cur)} leaves, artifact was captured "
                f"with {len(stored)}")
    for i, (s, c) in enumerate(zip(stored, cur)):
        if list(s[0]) != list(c[0]) or str(s[1]) != str(c[1]):
            return (f"input leaf {i}: artifact aval "
                    f"{tuple(s[0])}/{s[1]} vs current {tuple(c[0])}/{c[1]}")
    return None


def _collect_autotune_configs() -> Dict[str, Dict[str, Any]]:
    """Snapshot the autotuner's in-memory + on-disk winners — the block
    configs the captured module was traced with (docs/perf.md).  Purely
    informational at load time (the module already baked them in), but
    a retarget/substitution rebuild on another box re-tunes from these."""
    out: Dict[str, Dict[str, Any]] = {}
    try:
        from ..ops.pallas import autotune as _at
        with _at._LOCK:
            mem = dict(_at._MEM)
        for key, cfg in mem.items():
            op = key.split("|", 1)[0]
            out.setdefault(op, {})[key] = dict(cfg)
        for op in _at.tunables():
            for key, entry in _at._disk_load(op).items():
                if isinstance(entry.get("config"), dict):
                    out.setdefault(op, {}).setdefault(
                        key, {k: int(v)
                              for k, v in entry["config"].items()})
    except Exception:
        pass
    return out


class ExportArtifact:
    """In-memory view of one artifact directory (manifest + modules).

    Construct empty via `ExportArtifact.new(kind)`, add modules with
    `add_module`, persist with `save(path)`; or read one back with
    `ExportArtifact.read(path)` and fetch the module for the current
    topology with `module_bytes(...)`."""

    def __init__(self, manifest: Dict[str, Any],
                 modules: Dict[str, bytes],
                 params: Optional[Dict[str, Any]] = None,
                 path: Optional[str] = None):
        self.manifest = manifest
        self._modules = modules        # mkey -> serialized jax.export blob
        self.params = params           # block captures: {name: host array}
        self.path = path

    # -- construction ----------------------------------------------------
    @classmethod
    def new(cls, kind: str, model_meta: Optional[dict] = None
            ) -> "ExportArtifact":
        import jax
        manifest = {
            "format_version": FORMAT_VERSION,
            "kind": kind,
            "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "jax_version": jax.__version__,
            "model": model_meta or {},
            "remat_policy": None,
            "autotune_configs": _collect_autotune_configs(),
            "modules": {},
            "passes": [],
            "meta": {},
        }
        return cls(manifest, {}, None, None)

    def add_module(self, blob: bytes, topology: Dict[str, Any],
                   in_avals, batch_avals=None, batch_specs=None,
                   platforms: Sequence[str] = (), tag: str = "",
                   meta: Optional[dict] = None) -> str:
        """Register one serialized module; returns its key.  Re-adding a
        key overwrites (a rewrite pass replacing the module)."""
        mkey = topology_key(topology, tag)
        self._modules[mkey] = blob
        self.manifest["modules"][mkey] = {
            "file": f"module_{mkey}.stablehlo",
            "topology": {"devices": int(topology.get("devices", 1)),
                         "axes": {str(k): int(v) for k, v in
                                  (topology.get("axes") or {}).items()}},
            "platforms": list(platforms),
            "sha256": hashlib.sha256(blob).hexdigest(),
            "in_avals": _aval_list(in_avals),
            "batch_avals": (None if batch_avals is None
                            else _aval_list(batch_avals)),
            "batch_specs": (None if batch_specs is None else
                            [[None if a is None else a for a in spec]
                             for spec in batch_specs]),
            "meta": meta or {},
        }
        return mkey

    def record_pass(self, name: str, **info) -> None:
        self.manifest["passes"].append({"name": name, **info})

    @property
    def kind(self) -> str:
        return self.manifest.get("kind", "?")

    @property
    def module_keys(self) -> List[str]:
        return sorted(self.manifest.get("modules", {}))

    def artifact_hash(self) -> str:
        """sha256 over every module blob (sorted by key) — the compile
        -cache-adjacent identity of this artifact."""
        h = hashlib.sha256()
        for mkey in sorted(self._modules):
            h.update(mkey.encode())
            h.update(self._modules[mkey])
        return h.hexdigest()

    # -- persistence -----------------------------------------------------
    def save(self, path: str) -> str:
        """Write the artifact directory atomically enough for concurrent
        replicas: modules land under temp names first, the manifest
        (naming the final files) goes last."""
        from .. import telemetry as _tele
        t0 = time.perf_counter()
        os.makedirs(path, exist_ok=True)
        self.manifest["hash"] = self.artifact_hash()
        for mkey, blob in self._modules.items():
            fn = self.manifest["modules"][mkey]["file"]
            tmp = os.path.join(path, f".{fn}.tmp.{os.getpid()}")
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, os.path.join(path, fn))
        if self.params is not None:
            import numpy as onp
            from ..util import npz_encode_entry
            out: Dict[str, Any] = {}
            for n, v in self.params.items():
                npz_encode_entry(out, n, onp.asarray(v))
            tmp = os.path.join(path, f".{_PARAMS}.tmp.{os.getpid()}")
            with open(tmp, "wb") as f:
                onp.savez(f, **out)
            os.replace(tmp, os.path.join(path, _PARAMS))
        tmp = os.path.join(path, f".{_MANIFEST}.tmp.{os.getpid()}")
        with open(tmp, "w") as f:
            json.dump(self.manifest, f, indent=1, sort_keys=True)
        os.replace(tmp, os.path.join(path, _MANIFEST))
        self.path = path
        if _tele.enabled():
            _tele.histogram(
                "export_capture_ms",
                "Wall time of one export capture+save (offline)"
            ).observe((time.perf_counter() - t0) * 1e3)
            _tele.event("export", phase="save", path=path,
                        kind=self.kind, modules=self.module_keys,
                        hash=self.manifest["hash"][:16])
        return path

    @classmethod
    def read(cls, path: str) -> "ExportArtifact":
        """Read manifest + module blobs; validates version and per-file
        hashes (a truncated module must fail here, not inside XLA)."""
        mpath = os.path.join(path, _MANIFEST)
        if not os.path.isfile(mpath):
            raise MXNetError(
                f"no export artifact at {path!r} (missing {_MANIFEST}); "
                "expected a directory written by export.capture(...).save")
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise MXNetError(f"unreadable export manifest {mpath}: {e}")
        ver = manifest.get("format_version")
        if ver != FORMAT_VERSION:
            raise MXNetError(
                f"export artifact {path} has format_version={ver!r}; this "
                f"build speaks version {FORMAT_VERSION}. Re-capture the "
                "artifact with the current code (stale-version artifacts "
                "are never loaded best-effort).")
        modules: Dict[str, bytes] = {}
        for mkey, rec in manifest.get("modules", {}).items():
            fp = os.path.join(path, rec["file"])
            try:
                with open(fp, "rb") as f:
                    blob = f.read()
            except OSError as e:
                raise MXNetError(
                    f"export artifact {path} names module {rec['file']} "
                    f"which cannot be read: {e}")
            digest = hashlib.sha256(blob).hexdigest()
            if digest != rec.get("sha256"):
                raise MXNetError(
                    f"export artifact module {rec['file']} is corrupt: "
                    f"sha256 {digest[:16]}… != manifest "
                    f"{str(rec.get('sha256'))[:16]}…")
            modules[mkey] = blob
        params = None
        ppath = os.path.join(path, _PARAMS)
        if os.path.isfile(ppath):
            import numpy as onp
            from ..util import npz_decode_entry
            with onp.load(ppath, allow_pickle=False) as z:
                params = dict(npz_decode_entry(k, z[k]) for k in z.files)
        return cls(manifest, modules, params, path)

    # -- lookup ----------------------------------------------------------
    def module_record(self, topology: Dict[str, Any], tag: str = ""
                      ) -> Dict[str, Any]:
        mkey = topology_key(topology, tag)
        rec = self.manifest.get("modules", {}).get(mkey)
        if rec is None:
            have = ", ".join(self.module_keys) or "<none>"
            raise MXNetError(
                f"export artifact {self.path or '<mem>'} has no module for "
                f"topology {mkey!r} (captured: {have}). Run the "
                "ShardingRetargetPass offline to add this topology, or "
                "re-capture under the current mesh (docs/export.md "
                "failure matrix).")
        return rec

    def module_bytes(self, topology: Dict[str, Any], tag: str = "") -> bytes:
        mkey = topology_key(topology, tag)
        self.module_record(topology, tag)   # raises the clear error
        return self._modules[mkey]

    def check_avals(self, topology: Dict[str, Any], args_tree,
                    tag: str = "") -> None:
        """Fail fast (MXNetError naming the drifted leaf) when the live
        input tree no longer matches the captured avals."""
        rec = self.module_record(topology, tag)
        bad = _aval_mismatch(rec["in_avals"], args_tree)
        if bad:
            raise MXNetError(
                f"export artifact {self.path or '<mem>'} "
                f"[{topology_key(topology, tag)}] does not match the "
                f"current inputs: {bad}. Re-capture (or re-run the "
                "rewrite pipeline) for the new shapes/dtypes.")
