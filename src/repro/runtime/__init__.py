from .serve import (
    BatchingEngine,
    Request,
    ServeConfig,
    ServedTrace,
    ServeLoopConfig,
    VirtualClock,
    choose_batch_size,
    plan_aware_batch_size,
    serve_trace,
)
from .traffic import (
    DeadlineClass,
    DiurnalProcess,
    FlashCrowdProcess,
    PoissonProcess,
    Trace,
    make_trace,
)
