"""Build hook: bundle (and pre-compile) the native batch-crypto library.

The C++ batch verifier lives at native/secp256k1.cc in the repo layout
(built lazily by babble_tpu/native_crypto.py in dev checkouts). Wheels
must be self-contained, so build_py copies the source into
babble_tpu/_native/ and, when a C++ compiler is available, pre-compiles
the library there too, under the content-hash name native_crypto.py
loads — installs without a toolchain still work (native_crypto builds on
first use, or falls back to the OpenSSL path).
All metadata is in pyproject.toml; this file only customizes the build.
"""

import os
import shutil
import subprocess

from setuptools import setup
from setuptools.command.build_py import build_py


class BuildPyWithNative(build_py):
    def run(self):
        super().run()
        here = os.path.dirname(os.path.abspath(__file__))
        src = os.path.join(here, "native", "secp256k1.cc")
        if not os.path.exists(src):
            return
        dest_dir = os.path.join(self.build_lib, "babble_tpu", "_native")
        os.makedirs(dest_dir, exist_ok=True)
        shutil.copy2(src, dest_dir)
        import hashlib

        # same naming rule as babble_tpu.native_crypto.so_name (not imported:
        # the package may not be importable at build time)
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so = os.path.join(dest_dir, f"libbabble_crypto.{digest}.so")
        try:
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", so,
                 os.path.join(dest_dir, "secp256k1.cc")],
                check=True,
                capture_output=True,
                timeout=120,
            )
        except (OSError, subprocess.SubprocessError):
            pass  # runtime lazy build takes over


setup(cmdclass={"build_py": BuildPyWithNative})
