"""Build/install — ≙ the reference's ``setup.py`` (L0).

The reference conditionally compiles ~20 CUDA extensions behind flags
(``--cpp_ext --cuda_ext --fmha ...``).  Here the device side is JAX/XLA/
Pallas (nothing to compile), and the one native piece — the host-ops
library (flatten/unflatten, masked-LM input pipeline;
``apex_tpu/_native/host_ops.cpp``) — is built on first import with a
graceful numpy fallback, so a plain ``pip install .`` always works.
``python setup.py build_native`` prebuilds it eagerly (the ``--cpp_ext``
analog).
"""

import subprocess
import sys

from setuptools import Command, find_packages, setup


class build_native(Command):
    """Eagerly compile the host-ops library (≙ ``--cpp_ext``)."""

    description = "compile apex_tpu/_native/host_ops.cpp"
    user_options = []

    def initialize_options(self):
        pass

    def finalize_options(self):
        pass

    def run(self):
        code = subprocess.call(
            [
                sys.executable,
                "-c",
                "import apex_tpu._native as n; n._load(); "
                "print('native available:', n.NATIVE_AVAILABLE)",
            ]
        )
        if code:
            raise SystemExit(code)


setup(
    name="apex_tpu",
    version="0.1.0",
    description=(
        "TPU-native training-acceleration framework with the capabilities "
        "of NVIDIA Apex: fused ops (Pallas), fused optimizers, precision "
        "policies, and dp/tp/sp/pp/cp parallelism over a jax.sharding.Mesh"
    ),
    packages=find_packages(include=["apex_tpu", "apex_tpu.*"]),
    package_data={"apex_tpu._native": ["host_ops.cpp"]},
    python_requires=">=3.10",
    # The one installation the code is written for and tested on (jax /
    # jaxlib 0.9.0, libtpu 0.0.34, flax 0.12.3, optax 0.2.6).  It leans
    # on what 0.9 ships natively — jax.shard_map with vma typing,
    # lax.axis_size / lax.pcast, jax.extend.core, pltpu.CompilerParams —
    # and carries no shims for older releases.
    install_requires=[
        "jax>=0.9,<0.10", "flax>=0.12,<0.13", "optax>=0.2.6",
        "numpy", "einops",
    ],
    cmdclass={"build_native": build_native},
)
