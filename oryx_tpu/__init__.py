"""oryx_tpu — a TPU-native lambda-architecture ML framework.

A from-scratch rebuild of the capabilities of Oryx 2 (reference:
/root/reference, see SURVEY.md): a batch layer that periodically rebuilds
models from all historical data, a speed layer that produces incremental
model updates within seconds, and a horizontally scalable REST serving
layer — shipping end-to-end applications for ALS collaborative filtering,
k-means clustering, and random-decision-forest classification/regression.

Where the reference composes Spark + Kafka + HDFS + Tomcat on the JVM,
this framework is JAX/XLA-native: trainers are jit/shard_map programs over
a TPU device mesh, incremental updates (ALS fold-in, centroid drift, leaf
refresh) run on-device, and models flow between layers over a pluggable
message bus speaking the same MODEL / MODEL-REF / UP protocol with
PMML-compatible artifacts.
"""

__version__ = "0.1.0"
