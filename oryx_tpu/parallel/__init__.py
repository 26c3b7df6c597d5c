"""Device mesh + sharding helpers (the Spark-cluster analogue).

Where the reference distributes work as Spark RDD partitions over YARN
executors (SURVEY.md §2.12), this framework shards arrays over a
jax.sharding.Mesh and lets XLA insert ICI/DCN collectives.
"""

from oryx_tpu.parallel.mesh import (  # noqa: F401
    get_mesh,
    replicated,
    shard_rows,
)
