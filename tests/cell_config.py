"""A benchmark cell's configuration file, as the model tests that are split
over several files (``tests/test_qwen3_next*.py``, ``tests/test_laguna*.py``)
read it."""

import json
import os

from benchmark import manifest


def config_file(name):
    with open(os.path.join(manifest.HERE, "configs", name + ".json")) as f:
        return json.load(f)
