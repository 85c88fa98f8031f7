"""Process-level configuration: the framework's single documented flag registry.

Reference parity: ND4J's ``ND4JSystemProperties`` / ``ND4JEnvironmentVars``
(nd4j-common, org.nd4j.common.config) and libnd4j's ``Environment`` singleton
(libnd4j/include/system/Environment.h) expose debug/verbose/profiling switches,
memory limits, and backend selection as JVM system properties + env vars.

TPU-native realization: one Python singleton backed by ``DL4J_TPU_*`` env vars,
plus passthroughs to the JAX config plane (``jax_debug_nans``,
``jax_default_matmul_precision``) which play the role the CUDA environment
(CudaEnvironment.getConfiguration()) played in the reference.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional

_PREFIX = "DL4J_TPU_"


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(_PREFIX + name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _env_str(name: str, default: str) -> str:
    return os.environ.get(_PREFIX + name, default)


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(_PREFIX + name)
    return int(v) if v is not None else default


@dataclasses.dataclass
class Environment:
    """Global runtime flags. Mirrors libnd4j Environment + ND4JSystemProperties.

    Access via :func:`environment` — a process-wide singleton.
    """

    # -- debug plane (libnd4j Environment::setDebug/setVerbose) --------------
    debug: bool = dataclasses.field(default_factory=lambda: _env_bool("DEBUG", False))
    verbose: bool = dataclasses.field(default_factory=lambda: _env_bool("VERBOSE", False))
    # NaN/Inf panic: ND4J OpProfiler checkForNAN/checkForINF analog; routes to
    # jax.config.jax_debug_nans when enabled.
    check_nan: bool = dataclasses.field(default_factory=lambda: _env_bool("CHECK_NAN", False))

    # -- numeric policy -------------------------------------------------------
    # Default floating dtype for parameters (DL4J: DataType.FLOAT default;
    # gradient checks switch to DOUBLE — tests do the same via set_default_dtype).
    default_dtype: str = dataclasses.field(default_factory=lambda: _env_str("DTYPE", "float32"))
    # Compute dtype for matmul/conv-heavy paths; bfloat16 keeps the MXU fed.
    compute_dtype: str = dataclasses.field(default_factory=lambda: _env_str("COMPUTE_DTYPE", "bfloat16"))
    matmul_precision: str = dataclasses.field(
        default_factory=lambda: _env_str("MATMUL_PRECISION", "default")
    )

    # -- layout policy (SURVEY §8.3 hard part 3) ------------------------------
    # Reference is NCHW-default (cuDNN heritage). Internally we are NHWC for
    # TPU-friendly layouts; NCHW is accepted at the API edge and transposed.
    prefer_nhwc: bool = dataclasses.field(default_factory=lambda: _env_bool("PREFER_NHWC", True))

    # -- profiling plane (OpProfiler / ProfilingListener) ---------------------
    profiling: bool = dataclasses.field(default_factory=lambda: _env_bool("PROFILING", False))
    profile_dir: str = dataclasses.field(default_factory=lambda: _env_str("PROFILE_DIR", "/tmp/dl4j_tpu_profile"))

    # -- platform-helper selection (cuDNN helper analog, SURVEY §3.1) ---------
    # "auto": pick Pallas kernels on TPU where registered, XLA elsewhere.
    # "xla": force XLA lowering. "pallas": force custom kernels where available.
    helper_mode: str = dataclasses.field(default_factory=lambda: _env_str("HELPERS", "auto"))
    log_helper_selection: bool = dataclasses.field(
        default_factory=lambda: _env_bool("LOG_HELPERS", False)
    )

    # -- distributed ----------------------------------------------------------
    coordinator_address: Optional[str] = dataclasses.field(
        default_factory=lambda: os.environ.get(_PREFIX + "COORDINATOR") or None
    )
    num_processes: int = dataclasses.field(default_factory=lambda: _env_int("NUM_PROCESSES", 1))
    process_id: int = dataclasses.field(default_factory=lambda: _env_int("PROCESS_ID", 0))

    def apply_jax_config(self) -> None:
        """Push flags into the JAX config plane. Call once at startup."""
        import jax

        if self.check_nan:
            jax.config.update("jax_debug_nans", True)
        if self.matmul_precision != "default":
            jax.config.update("jax_default_matmul_precision", self.matmul_precision)
        if self.default_dtype == "float64":
            jax.config.update("jax_enable_x64", True)

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


_INSTANCE: Optional[Environment] = None


def environment() -> Environment:
    """The process-wide Environment singleton (libnd4j Environment::getInstance)."""
    global _INSTANCE
    if _INSTANCE is None:
        _INSTANCE = Environment()
    return _INSTANCE


def reset_environment() -> Environment:
    """Re-read env vars (tests only)."""
    global _INSTANCE
    _INSTANCE = Environment()
    return _INSTANCE


# ---------------------------------------------------------------------------
# JAX's persistent compilation cache — THE one place that turns it on
# ---------------------------------------------------------------------------

# what this code writes beside itself goes to fixed, git-ignored directories
# at the root of the checkout — never the user's home, never a name made from
# tempfile, a pid or the time (a cache directory that moves never hits)
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# where compiled programs persist when JAX_COMPILATION_CACHE_DIR is unset
_CHECKOUT_COMPILE_CACHE = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads that
    directory from the environment and no code sets another; where it is
    not, the cache lives in ``.jax_cache/`` inside the checkout. Every
    program is cached, however small or fast to compile: a cold process
    (``chip_smoke.py``, ``benchmarks/run.py``, an AOT warm boot) pays for
    each one again otherwise."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not jax.config.jax_enable_compilation_cache:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    return cache_dir


def disable_compile_cache() -> None:
    """Stop reading and writing the persistent cache in this process
    (tests: later compiles must not keep serializing binaries to disk)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
