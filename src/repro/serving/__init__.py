from repro.parallel.sharding import (
    SamplerShardings,
    SamplerSpecs,
    sampler_pspecs,
    sampler_shardings,
)
from repro.serving import result_keys
from repro.serving.compile_cache import (
    cache_dir,
    configure_persistent_cache,
    disk_cache_hits,
)
from repro.serving.engine import Engine, ServeConfig, cache_slots, resolve_window
from repro.serving.executor import (
    DEFAULT_MAX_BATCH,
    DEFAULT_MAX_NFE,
    DEFAULT_MAX_SEQ_LEN,
    SEED_MAX,
    SEED_MIN,
    FusedExecutor,
    SampleRequest,
    SampleResult,
)
from repro.serving.factory import (
    WARMUP_MODES,
    EngineConfig,
    build_engine,
    make_solver_config,
    warmup_kwargs,
)
from repro.serving.frontdoor import (
    SCHEMA_VERSION,
    FrontDoor,
    FrontDoorClient,
    SchemaError,
    decode_request,
    decode_result,
    encode_request,
    encode_result,
    serve_frontdoor,
)
from repro.serving.metrics import MetricsRegistry
from repro.serving.scheduler import (
    AsyncBatchedSampler,
    DeadlineExceededError,
    QueueFullError,
    SchedulerPolicy,
    open_loop,
)

__all__ = [
    "DEFAULT_MAX_BATCH",
    "DEFAULT_MAX_NFE",
    "DEFAULT_MAX_SEQ_LEN",
    "SCHEMA_VERSION",
    "SEED_MAX",
    "SEED_MIN",
    "AsyncBatchedSampler",
    "DeadlineExceededError",
    "Engine",
    "EngineConfig",
    "FrontDoor",
    "FrontDoorClient",
    "FusedExecutor",
    "MetricsRegistry",
    "QueueFullError",
    "SampleRequest",
    "SampleResult",
    "SamplerShardings",
    "SamplerSpecs",
    "SchedulerPolicy",
    "SchemaError",
    "ServeConfig",
    "WARMUP_MODES",
    "build_engine",
    "cache_dir",
    "cache_slots",
    "configure_persistent_cache",
    "decode_request",
    "decode_result",
    "disk_cache_hits",
    "encode_request",
    "encode_result",
    "make_solver_config",
    "open_loop",
    "resolve_window",
    "result_keys",
    "sampler_pspecs",
    "sampler_shardings",
    "serve_frontdoor",
    "warmup_kwargs",
]
