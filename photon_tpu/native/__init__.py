"""Native runtime components, built lazily with the system toolchain.

The compute path is JAX/XLA; the HOST runtime around it (here: the Avro
block decoder feeding ingest) is native C, mirroring how the reference
leans on the JVM Avro runtime's generated decoders (AvroUtils.scala:62)
rather than interpreting schemas per record.

``get_avro_decoder()`` compiles ``avrodec.c`` into
``<checkout>/.native_cache`` on first use (source-hash keyed, so edits
rebuild) and returns the extension module, or None when no working
compiler is available — callers fall back to the interpreter codec (and
a WARNING says so), so the native layer is a pure accelerator, never a
dependency.
"""

from __future__ import annotations

import hashlib
import importlib.util
import logging
import os
import subprocess
import sysconfig

from photon_tpu import CHECKOUT_ROOT

logger = logging.getLogger(__name__)

_SOURCE = os.path.join(os.path.dirname(__file__), "avrodec.c")
_cached = None
_failed = False


def _cache_dir() -> str:
    # Built inside the checkout (git-ignored), not under ~: a sealed
    # machine's home is new on every run, and an object file left in a
    # shared home by another machine is not this checkout's build.
    base = os.environ.get(
        "PHOTON_NATIVE_CACHE", os.path.join(CHECKOUT_ROOT, ".native_cache")
    )
    os.makedirs(base, exist_ok=True)
    return base


def _build() -> str | None:
    with open(_SOURCE, "rb") as f:
        src = f.read()
    tag = hashlib.blake2b(
        src + sysconfig.get_config_var("EXT_SUFFIX").encode(),
        digest_size=8,
    ).hexdigest()
    ext = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    out = os.path.join(_cache_dir(), f"photon_avrodec_{tag}{ext}")
    if os.path.exists(out):
        return out
    include = sysconfig.get_paths()["include"]
    cc = os.environ.get("CC", "cc")
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = [cc, "-O2", "-fPIC", "-shared", f"-I{include}", _SOURCE, "-o", tmp]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, timeout=120,
        )
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        logger.warning(
            "native avro decoder unavailable (%s: %s); falling back to the "
            "interpreter codec", e, detail.decode(errors="replace")[:500],
        )
        # A failed compile can leave a partial object behind; the tmp name
        # is per-pid, so stragglers would accumulate in the shared cache.
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    os.replace(tmp, out)
    return out


def get_avro_decoder():
    """The compiled ``photon_avrodec`` module, or None (fallback)."""
    global _cached, _failed
    if _cached is not None or _failed:
        return _cached
    path = None
    try:
        path = _build()
        if path is None:
            _failed = True
            return None
        spec = importlib.util.spec_from_file_location("photon_avrodec", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _cached = mod
    except Exception as e:  # any load failure -> interpreter fallback
        logger.warning("native avro decoder failed to load (%s)", e)
        # A corrupted cache file would otherwise poison every later
        # process; drop it so the next call rebuilds from source.
        try:
            if path is not None:
                os.unlink(path)
        except OSError:
            pass
        _failed = True
        return None
    return _cached
