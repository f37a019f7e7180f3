"""AOT warm bundles: serialized compiled stages for restart-proof serving.

A node restarted mid-slot eats the cold-shape XLA cost for every bucket
it serves — and on this codebase the dominant term is host-side TRACE +
LOWER of the ~60k-op verification stages (minutes per shape on a 1-core
host; the persistent compilation cache only skips the XLA optimization
that follows). The warm bundle closes that gap: a producer process
(`scripts/make_warm_bundle.py`) exports each pipeline stage via
`jax.export` (StableHLO, shape-exact) into a versioned on-disk bundle
with a manifest + content hashes; a fresh process deserializes the
artifact (milliseconds) and jits the embedded module — skipping the
retrace entirely and hitting the persistent compile cache for the
optimization step — so its first full-size batch is served in seconds.

Bundle layout (`<dir>/manifest.json` + content-addressed artifacts):

    manifest.json   {"bundle_version", "jax_version", "platform",
                     "entries": {core_key: {"stages": [avals_key...],
                                            "export_secs": [...]}},
                     "stages": {avals_key: {"file", "sha256", "size"}}}
    <sha256>.bin    one serialized `jax.export.Exported` per stage graph

Core keys are `(layout, n_bucket, k_bucket, m_bucket, sharded)`; stage
artifacts are keyed (and deduped) by their exact input-aval signature,
so e.g. the pairing stage for n=4096 is stored once no matter how many
(k, m) cores reference it.

Consumers integrate at the STAGE level: `stage_dispatch` wraps a
production stage jit so that any call whose concrete aval signature has
a bundle artifact is served from the deserialized export, and every
other call falls through to the normal trace-and-compile path. A stale
manifest (bundle/jax version or platform mismatch) deactivates the whole
bundle; a corrupt artifact (hash mismatch, deserialization failure)
deactivates that one entry — both fall back to the compile path and are
counted in `stats()` / the serving metrics.

The bundle is resolved from `LIGHTHOUSE_TPU_WARM_BUNDLE` (a directory
path; unset = no bundle, zero behavior change) or installed explicitly
with `set_active_bundle` (tests, probes).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

BUNDLE_VERSION = 1
MANIFEST_NAME = "manifest.json"
ENV_VAR = "LIGHTHOUSE_TPU_WARM_BUNDLE"

DEFAULT_BUNDLE_DIR = os.path.expanduser("~/.cache/lighthouse_tpu_warm_bundle")


# ---------------------------------------------------------------------------
# Stats (read by ShapeWarmer, the restart probe, and serving metrics)
# ---------------------------------------------------------------------------


@dataclass
class BundleStats:
    hits: int = 0          # stage calls served from a bundle artifact
    misses: int = 0        # stage avals with no artifact (compile path)
    corrupt: int = 0       # artifacts rejected: hash/deserialize failure
    stale: int = 0         # whole-bundle rejections (version/platform)


_STATS = BundleStats()
_STATS_LOCK = threading.Lock()


def stats() -> BundleStats:
    with _STATS_LOCK:
        return BundleStats(_STATS.hits, _STATS.misses, _STATS.corrupt,
                           _STATS.stale)


def reset_stats() -> None:
    with _STATS_LOCK:
        _STATS.hits = _STATS.misses = _STATS.corrupt = _STATS.stale = 0


# Bundle outcomes double as executable-provenance events in the
# observability layer's vocabulary (observability/compile_events.py).
_PROVENANCE = {
    "hits": "warm_bundle_hit",
    "misses": "warm_bundle_miss",
    "corrupt": "bundle_corrupt",
    "stale": "bundle_stale",
}


def _count(attr: str, n: int = 1) -> None:
    with _STATS_LOCK:
        setattr(_STATS, attr, getattr(_STATS, attr) + n)
    try:  # serving metrics ride the global registry (scrape endpoint)
        from lighthouse_tpu.common.metrics import REGISTRY

        REGISTRY.counter_vec(
            "serving_bundle_stage_total",
            "Warm-bundle stage resolutions by outcome", "outcome",
        ).labels(attr).inc(n)
    except Exception:  # metrics are observability only
        pass
    try:
        from lighthouse_tpu.observability import compile_events

        for _ in range(n):
            compile_events.record(_PROVENANCE[attr])
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------


def avals_key(layout: str, stage_id: str, avals) -> str:
    """Content key for one stage graph: layout + stage id + the exact
    input aval signature (shape/dtype per argument). The stage id carries
    anything the graph depends on that the avals don't show (e.g. the BM
    prep stage's chunk width). The producer computes the key from export
    avals, the consumer from concrete call arguments — both through this
    one function, so they can never disagree."""
    sig = [[str(getattr(a, "dtype", "?")), list(getattr(a, "shape", ()))]
           for a in avals]
    blob = json.dumps([layout, stage_id, sig], separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def core_key(layout: str, n_bucket: int, k_bucket: int, m_bucket: int,
             sharded: bool = False) -> str:
    return f"{layout}|n={n_bucket}|k={k_bucket}|m={m_bucket}" \
           f"|sharded={int(bool(sharded))}"


# ---------------------------------------------------------------------------
# Layout registry: how to build each engine's exportable stages
# ---------------------------------------------------------------------------


@dataclass
class LayoutSpec:
    """One engine layout's export recipe. `stages(n, k, m)` returns
    per-stage (stage_id, callable, input-avals) triples — the stage_id
    must match what the engine's dispatch wrappers pass at serve time;
    `m_menu(n)` is the distinct-message bucket menu staged for an n
    bucket (the production staging menu, so the bundle can never desync
    from what serving will request)."""

    name: str
    stages: Callable[[int, int, int], List[Tuple[str, Callable, tuple]]]
    m_menu: Callable[[int], List[int]]


def _backend_m_menu(n_bucket: int) -> List[int]:
    from lighthouse_tpu.ops.backend import M_BUCKET_SHIFTS

    return sorted({max(1, n_bucket >> s) for s in M_BUCKET_SHIFTS})


def _bm_m_menu(n_bucket: int) -> List[int]:
    from lighthouse_tpu.ops.backend import bm_m_menu

    return bm_m_menu(n_bucket)


def _major_stages(n: int, k: int, m: int):
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import backend as be
    from lighthouse_tpu.ops import limbs as lb

    S, D = jax.ShapeDtypeStruct, lb.DTYPE
    return [
        ("h2g2", be._h2g2_gather,
         (S((m, 2, 2, lb.L), D), S((n,), jnp.int32))),
        ("prepare", be._prepare_pairs,
         (S((n, k, 3, lb.L), D), S((n, 3, 2, lb.L), D),
          S((n,), jnp.bool_), S((n,), jnp.bool_), S((n,), jnp.uint64))),
        ("pairing", be._pairing_check,
         (S((n + 1, 3, lb.L), D), S((n, 3, 2, lb.L), D),
          S((3, 2, lb.L), D), S((n,), jnp.bool_), S((), jnp.bool_))),
    ]


def _bm_stages(n: int, k: int, m: int):
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops.bm import backend as bmb
    from lighthouse_tpu.ops.bm import limbs as lb

    S, D = jax.ShapeDtypeStruct, lb.DTYPE
    prep_chunk = bmb.prep_chunk_width(n)
    return [
        ("h2g2", bmb._h2g2, (S((2, 2, lb.L, m), D),)),
        # The prep graph depends on the chunk width (a lax.scan over
        # slabs vs one monolithic pass) — the id carries it because the
        # input avals can't.
        (f"prepare:c{prep_chunk}", bmb._make_prepare(m, prep_chunk),
         (S((k, 3, lb.L, n), D), S((3, 2, lb.L, n), D),
          S((n,), jnp.bool_), S((n,), jnp.bool_), S((n,), jnp.uint64),
          S((n,), jnp.int32))),
        ("pairing", bmb._miller_product,
         (S((3, lb.L, m + 1), D), S((3, 2, lb.L, m), D),
          S((3, 2, lb.L, 1), D), S((m,), jnp.bool_))),
        ("final_exp", bmb._final_check,
         (S(bmb.FP12_SHAPE, D), S((), jnp.bool_))),
    ]


_LAYOUTS: Dict[str, LayoutSpec] = {
    "major": LayoutSpec("major", _major_stages, _backend_m_menu),
    "bm": LayoutSpec("bm", _bm_stages, _bm_m_menu),
}


def register_layout(spec: LayoutSpec) -> None:
    """Register an engine layout's export recipe (tests register tiny
    synthetic layouts so the bundle machinery is exercised without paying
    the minutes-long trace of the real pipeline stages)."""
    _LAYOUTS[spec.name] = spec


def get_layout(name: str) -> LayoutSpec:
    return _LAYOUTS[name]


# ---------------------------------------------------------------------------
# Reading: WarmBundle
# ---------------------------------------------------------------------------


class WarmBundle:
    """An opened, validated bundle directory. Use `open_bundle` — it
    returns None (and counts `stale`) instead of raising on any
    version/platform mismatch or unreadable manifest."""

    def __init__(self, path: str, manifest: dict):
        self.path = path
        self.manifest = manifest
        self._loaded: Dict[str, Optional[Callable]] = {}
        self._lock = threading.Lock()

    # -------------------------------------------------------------- queries

    @property
    def entries(self) -> Dict[str, dict]:
        return self.manifest.get("entries", {})

    @property
    def policy(self) -> Optional[dict]:
        """The persisted autotune policy, if one was saved (see
        `save_policy` / serving/autotune.apply_policy)."""
        p = self.manifest.get("policy")
        return p if isinstance(p, dict) else None

    def has_stage(self, key: str) -> bool:
        return key in self.manifest.get("stages", {})

    def has_core(self, layout: str, n_bucket: int, k_bucket: int,
                 m_bucket: int, sharded: bool = False) -> bool:
        return core_key(layout, n_bucket, k_bucket, m_bucket,
                        sharded) in self.entries

    # -------------------------------------------------------------- loading

    def load_stage(self, key: str) -> Optional[Callable]:
        """Deserialize one stage artifact into a jitted callable; None on
        miss or corruption (hash mismatch / deserialize failure). Results
        (including negative ones) are cached for the process lifetime."""
        with self._lock:
            if key in self._loaded:
                return self._loaded[key]
        fn = self._load_stage_uncached(key)
        with self._lock:
            self._loaded[key] = fn
        return fn

    def _load_stage_uncached(self, key: str) -> Optional[Callable]:
        meta = self.manifest.get("stages", {}).get(key)
        if meta is None:
            return None
        fpath = os.path.join(self.path, meta["file"])
        try:
            blob = open(fpath, "rb").read()
        except OSError:
            _count("corrupt")
            logger.warning("warm bundle artifact unreadable: %s", fpath)
            return None
        if hashlib.sha256(blob).hexdigest() != meta["sha256"]:
            _count("corrupt")
            logger.warning("warm bundle artifact hash mismatch: %s", fpath)
            return None
        try:
            import jax
            from jax import export as jexport

            exported = jexport.deserialize(bytearray(blob))
            call = jax.jit(exported.call)
            call.in_avals = exported.in_avals
            return call
        except Exception:
            _count("corrupt")
            logger.warning("warm bundle artifact failed to deserialize: %s",
                           fpath, exc_info=True)
            return None

    def warm_core(self, layout: str, n_bucket: int, k_bucket: int,
                  sharded: bool = False,
                  m_menu: Optional[Sequence[int]] = None) -> bool:
        """The ShapeWarmer fast path: for every m bucket of the staging
        menu, load the (n, k, m) core's three stage artifacts and execute
        each once on zero tensors of its exact avals (a masked execution:
        the compile is the point, the semantics don't matter). True only
        if EVERY stage of every menu entry was served from the bundle —
        anything less and the caller must fall back to the compile path
        so the shape still warms."""
        try:
            spec = get_layout(layout)
        except KeyError:
            return False
        menu = list(m_menu) if m_menu is not None else spec.m_menu(n_bucket)
        for m_bucket in menu:
            key = core_key(layout, n_bucket, k_bucket, m_bucket, sharded)
            entry = self.entries.get(key)
            if entry is None:
                _count("misses")
                return False
            for stage_key in entry["stages"]:
                fn = self.load_stage(stage_key)
                if fn is None:
                    _count("misses")
                    return False
                if not _execute_on_zeros(fn):
                    _count("corrupt")
                    return False
                _count("hits")
        return True

    def verify(self) -> Tuple[int, int]:
        """Integrity sweep: (ok, bad) artifact counts. `bad` covers hash
        mismatches, unreadable files, and undeserializable blobs."""
        ok = bad = 0
        for key in self.manifest.get("stages", {}):
            if self.load_stage(key) is None:
                bad += 1
            else:
                ok += 1
        return ok, bad


def _execute_on_zeros(call) -> bool:
    """Run a loaded stage once on zeros of its recorded input avals (the
    kernels are branch-free; garbage inputs compile and execute exactly
    like real ones)."""
    try:
        import jax
        import jax.numpy as jnp

        args = [jnp.zeros(a.shape, a.dtype) for a in call.in_avals]
        jax.block_until_ready(call(*args))
        return True
    except Exception:
        logger.warning("warm bundle stage failed to execute", exc_info=True)
        return False


def open_bundle(path: str) -> Optional[WarmBundle]:
    """Open + validate a bundle directory; None when absent or stale
    (bundle-version / jax-version / platform mismatch — the compile path
    still works, so staleness is a fallback, never an error)."""
    mpath = os.path.join(path, MANIFEST_NAME)
    try:
        manifest = json.loads(open(mpath, "rb").read())
    except (OSError, ValueError):
        return None
    import jax

    if manifest.get("bundle_version") != BUNDLE_VERSION:
        _count("stale")
        logger.warning("warm bundle %s: version %r != %d", path,
                       manifest.get("bundle_version"), BUNDLE_VERSION)
        return None
    if manifest.get("jax_version") != jax.__version__:
        _count("stale")
        logger.warning("warm bundle %s: built for jax %r, running %s",
                       path, manifest.get("jax_version"), jax.__version__)
        return None
    if manifest.get("platform") != jax.default_backend():
        _count("stale")
        logger.warning("warm bundle %s: built for %r, running on %s", path,
                       manifest.get("platform"), jax.default_backend())
        return None
    return WarmBundle(path, manifest)


# ---------------------------------------------------------------------------
# Active bundle (process-wide; the stage_dispatch consult point)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[WarmBundle] = None
_ACTIVE_RESOLVED = False
_ACTIVE_LOCK = threading.Lock()


def active_bundle() -> Optional[WarmBundle]:
    """The process's warm bundle: resolved once from LIGHTHOUSE_TPU_WARM_
    BUNDLE (unset = None = compile path everywhere), or whatever
    `set_active_bundle` installed."""
    global _ACTIVE, _ACTIVE_RESOLVED
    if _ACTIVE_RESOLVED:
        return _ACTIVE
    with _ACTIVE_LOCK:
        if not _ACTIVE_RESOLVED:
            path = os.environ.get(ENV_VAR)
            _ACTIVE = open_bundle(path) if path else None
            _ACTIVE_RESOLVED = True
    return _ACTIVE


def set_active_bundle(bundle) -> Optional[WarmBundle]:
    """Install (or clear, with None) the process bundle explicitly. Accepts
    a WarmBundle or a directory path; returns what was installed."""
    global _ACTIVE, _ACTIVE_RESOLVED
    if isinstance(bundle, str):
        bundle = open_bundle(bundle)
    with _ACTIVE_LOCK:
        _ACTIVE = bundle
        _ACTIVE_RESOLVED = True
    return bundle


def reset_active_bundle() -> None:
    """Forget the resolution (tests; next access re-reads the env var)."""
    global _ACTIVE, _ACTIVE_RESOLVED
    with _ACTIVE_LOCK:
        _ACTIVE = None
        _ACTIVE_RESOLVED = False


def stage_dispatch(layout: str, stage_id: str,
                   fallback: Callable) -> Callable:
    """Wrap a production stage jit: calls whose concrete aval signature
    has an artifact in the active bundle run the deserialized export (no
    retrace); everything else falls through to `fallback`. With no active
    bundle the overhead is one None check per call."""

    def dispatch(*args):
        bundle = active_bundle()
        if bundle is not None:
            key = avals_key(layout, stage_id, args)
            if bundle.has_stage(key):
                fn = bundle.load_stage(key)
                if fn is not None:
                    _count("hits")
                    return fn(*args)
            _count("misses")
        return fallback(*args)

    dispatch.fallback = fallback
    return dispatch


# ---------------------------------------------------------------------------
# Writing: the producer (scripts/make_warm_bundle.py drives this)
# ---------------------------------------------------------------------------


@dataclass
class ExportReport:
    cores: int = 0
    stages_exported: int = 0      # fresh exports (deduped stages excluded)
    stages_reused: int = 0
    export_secs: float = 0.0
    bytes_written: int = 0
    errors: List[str] = field(default_factory=list)


def export_stage(fn: Callable, avals: tuple):
    """Trace + lower one stage to a serialized `jax.export` artifact.
    This is the cost the bundle front-loads: minutes per big shape."""
    import jax
    from jax import export as jexport

    exported = jexport.export(jax.jit(fn))(*avals)
    return exported.serialize()


def make_bundle(path: str, shapes: Sequence[Tuple[int, int]],
                layout: Optional[str] = None, sharded: bool = False,
                m_menu: Optional[Sequence[int]] = None,
                progress: Optional[Callable[[str], None]] = None,
                ) -> ExportReport:
    """Produce a warm bundle for a (n_bucket, k_bucket) shape grid.

    Exports each core's three stages for every m bucket of the staging
    menu, content-addresses the artifacts (identical stage graphs are
    stored once), and atomically writes the manifest last — a killed
    producer leaves either the previous valid bundle or loose orphan
    files, never a manifest referencing missing artifacts. Existing
    manifest entries for other shapes are preserved (incremental grows)."""
    import jax

    say = progress or (lambda s: None)
    os.makedirs(path, exist_ok=True)
    spec = get_layout(layout or _current_layout())
    report = ExportReport()

    old = None
    policy = None
    try:
        old = json.loads(open(os.path.join(path, MANIFEST_NAME), "rb").read())
        # The autotune policy is measured fact, not compiled code: it
        # survives even a stale rebuild that discards every artifact.
        policy = old.get("policy")
        if (old.get("bundle_version") != BUNDLE_VERSION
                or old.get("jax_version") != jax.__version__
                or old.get("platform") != jax.default_backend()):
            old = None  # stale: rebuild from scratch
    except (OSError, ValueError):
        pass
    entries = dict(old.get("entries", {})) if old else {}
    stage_files = dict(old.get("stages", {})) if old else {}

    for n_bucket, k_bucket in shapes:
        menu = list(m_menu) if m_menu is not None else spec.m_menu(n_bucket)
        for m_bucket in menu:
            ckey = core_key(spec.name, n_bucket, k_bucket, m_bucket, sharded)
            if ckey in entries:
                report.stages_reused += len(entries[ckey]["stages"])
                continue
            try:
                stage_list = spec.stages(n_bucket, k_bucket, m_bucket)
            except Exception as e:
                report.errors.append(f"{ckey}: stages: {e!r}")
                continue
            keys, secs = [], []
            failed = False
            for stage_id, fn, avals in stage_list:
                skey = avals_key(spec.name, stage_id, avals)
                keys.append(skey)
                if skey in stage_files:
                    report.stages_reused += 1
                    secs.append(0.0)
                    continue
                t0 = time.perf_counter()
                try:
                    blob = export_stage(fn, avals)
                except Exception as e:
                    report.errors.append(f"{ckey} {stage_id}: {e!r}")
                    failed = True
                    break
                dt = time.perf_counter() - t0
                digest = hashlib.sha256(blob).hexdigest()
                fname = f"{digest}.bin"
                fpath = os.path.join(path, fname)
                if not os.path.exists(fpath):
                    tmp = fpath + f".tmp{os.getpid()}"
                    with open(tmp, "wb") as f:
                        f.write(blob)
                    os.replace(tmp, fpath)
                    report.bytes_written += len(blob)
                stage_files[skey] = {
                    "file": fname, "sha256": digest, "size": len(blob),
                }
                report.stages_exported += 1
                report.export_secs += dt
                secs.append(round(dt, 3))
                say(f"  exported {ckey} {stage_id}: "
                    f"{len(blob)} bytes in {dt:.1f}s")
            if failed:
                continue
            entries[ckey] = {"stages": keys, "export_secs": secs}
            report.cores += 1

    manifest = {
        "bundle_version": BUNDLE_VERSION,
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "created": int(time.time()),
        "entries": entries,
        "stages": stage_files,
    }
    if isinstance(policy, dict):
        manifest["policy"] = policy
    _write_manifest(path, manifest)
    return report


def _write_manifest(path: str, manifest: dict) -> None:
    mpath = os.path.join(path, MANIFEST_NAME)
    tmp = mpath + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    os.replace(tmp, mpath)


# ---------------------------------------------------------------------------
# Autotune policy persistence (serving/autotune.py round-trips through here)
# ---------------------------------------------------------------------------


def save_policy(path: str, policy: dict) -> None:
    """Store an autotune policy under `manifest["policy"]`, preserving any
    existing bundle entries/stages (the producer and the autotuner share
    one manifest). Writes a skeleton manifest when none exists yet — a
    node can persist its learned policy before it ever exports a stage.
    No jax import: the serving control plane stays jax-free."""
    os.makedirs(path, exist_ok=True)
    try:
        manifest = json.loads(
            open(os.path.join(path, MANIFEST_NAME), "rb").read())
    except (OSError, ValueError):
        manifest = {"bundle_version": BUNDLE_VERSION,
                    "entries": {}, "stages": {}}
    manifest["policy"] = dict(policy)
    _write_manifest(path, manifest)


def load_policy(path: str) -> Optional[dict]:
    """Read back a persisted policy, or None. Deliberately NOT staleness-
    gated the way `open_bundle` is: the policy is measured fact about
    traffic and hardware, not compiled code — a jax upgrade invalidates
    the artifacts, not the measurements."""
    try:
        manifest = json.loads(
            open(os.path.join(path, MANIFEST_NAME), "rb").read())
    except (OSError, ValueError):
        return None
    policy = manifest.get("policy")
    return policy if isinstance(policy, dict) else None


def _current_layout() -> str:
    from lighthouse_tpu.ops.backend import _layout

    return _layout()
