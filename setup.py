"""Packaging for kungfu-tpu: pip-installable Python package + the libkf
C++ control plane built during the wheel build (reference: setup.py drives
CMake from pip the same way, /root/reference/setup.py:46-100; here the
native build is a plain Makefile since libkf has no external deps).

    pip install .          # builds kungfu_tpu/native/libkf.so in-tree
    kfrun -np 4 -- python train.py
    kfdistribute -H a:4,b:4 -- ...
"""

import subprocess

from setuptools import Command, Distribution, find_packages, setup
from setuptools.command.build_py import build_py


class BinaryDistribution(Distribution):
    """The wheel ships a platform-specific libkf.so, so it must carry a
    platform tag rather than py3-none-any. libkf is ctypes-loaded (no
    CPython ABI dependency), so the interpreter tag stays py3 — see the
    bdist_wheel get_tag override below."""

    def has_ext_modules(self):
        return True


try:
    from wheel.bdist_wheel import bdist_wheel

    class PlatWheel(bdist_wheel):
        def get_tag(self):
            _, _, plat = super().get_tag()
            return "py3", "none", plat

except ImportError:  # wheel not installed; sdist-only builds don't need it
    PlatWheel = None


class BuildNative(Command):
    """Build libkf.so via the native Makefile."""

    description = "build the libkf C++ control plane"
    user_options = []

    def initialize_options(self):
        pass

    def finalize_options(self):
        pass

    def run(self):
        subprocess.check_call(["make", "-C", "kungfu_tpu/native"])


class BuildPyWithNative(build_py):
    def run(self):
        self.run_command("build_native")
        super().run()


setup(
    name="kungfu-tpu",
    version="0.1.0",
    description=(
        "Adaptive, elastic, decentralized distributed training on TPU "
        "(JAX/XLA data plane + C++ DCN control plane)"
    ),
    packages=find_packages(include=["kungfu_tpu", "kungfu_tpu.*"]),
    package_data={
        "kungfu_tpu": ["native/libkf.so", "native/Makefile",
                       "native/include/*.h", "native/src/*"],
    },
    python_requires=">=3.11",
    # the one toolchain the tree is written and checked against (the
    # Pallas TPU names in ops/ are 0.9.0's; on a TPU host add
    # libtpu==0.0.34, i.e. `pip install "jax[tpu]==0.9.0"`)
    install_requires=["numpy", "jax==0.9.0", "jaxlib==0.9.0",
                      "flax==0.12.3", "optax"],
    distclass=BinaryDistribution,
    cmdclass={
        "build_native": BuildNative,
        "build_py": BuildPyWithNative,
        **({"bdist_wheel": PlatWheel} if PlatWheel else {}),
    },
    entry_points={
        "console_scripts": [
            "kfrun = kungfu_tpu.run.__main__:main",
            "kfdistribute = kungfu_tpu.run.distribute:main",
            "kf-config-server = kungfu_tpu.elastic.config_server:main",
        ],
    },
)
