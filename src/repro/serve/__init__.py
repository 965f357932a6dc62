from repro.serve.engine import (  # noqa: F401
    Engine,
    Request,
    ServeConfig,
)
from repro.serve.paging import (  # noqa: F401
    PageAllocator,
    PageTable,
    PrefixCache,
    hash_chunks,
    pages_needed,
)
from repro.serve.router import Router  # noqa: F401
from repro.serve.workload import run_timed_workload  # noqa: F401
