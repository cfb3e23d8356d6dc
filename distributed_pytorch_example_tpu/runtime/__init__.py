"""Runtime layer: process bootstrap, device mesh, rank-tagged logging.

TPU-native replacement for the reference's launch/communication layers
(reference entrypoint.sh:1-39 and train.py:70-98). One Python process per
host; devices join a global mesh; collectives are compiled by XLA.
"""

from distributed_pytorch_example_tpu.runtime.compile_cache import (  # noqa: F401
    enable_compile_cache,
)
from distributed_pytorch_example_tpu.runtime.distributed import (  # noqa: F401
    DistributedConfig,
    barrier,
    initialize,
    is_coordinator,
    process_count,
    process_index,
    shutdown,
)
from distributed_pytorch_example_tpu.runtime.mesh import (  # noqa: F401
    MeshSpec,
    make_mesh,
)
from distributed_pytorch_example_tpu.runtime.logging import (  # noqa: F401
    get_logger,
    setup_logging,
)
