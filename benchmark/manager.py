"""The model manager the benchmark gives the real ServingLayer through
`oryx.serving.model-manager-class`: it serves a model that a builder has
made from the seed, where a deployment's manager would replay the update
topic (copy of tools/load_benchmark.py LoadTestModelManager, the
reference's LoadTestALSModelFactory behind the real serving layer)."""

from __future__ import annotations


class BenchModelManager:
    def __init__(self, config) -> None:
        self._config = config
        self.model = None  # set by the harness before the first request

    def consume(self, it) -> None:
        for _ in it:
            pass

    def consume_blocks(self, it) -> None:
        for _ in it:
            pass

    def get_config(self):
        return self._config

    def get_model(self):
        return self.model

    def is_read_only(self) -> bool:
        return True

    def close(self) -> None:
        pass
