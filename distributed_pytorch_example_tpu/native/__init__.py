"""Native (C++) components, bound via ctypes with pure-Python fallbacks.

Build with ``make -C distributed_pytorch_example_tpu/native`` (binding.py
also auto-builds on first import when g++ is present; ``*.so`` is not
committed). Every binding has a bit-identical Python fallback for hosts
WITHOUT a toolchain — mirroring how the reference leans on PyTorch's
bundled native runtime without authoring native code itself (SURVEY.md
§2). Where ``g++`` and ``make`` exist, a failed build is an error.
"""

from __future__ import annotations

import shutil

_binding = None
_checked = False


def get_binding():
    """The loaded native binding module, or None when unavailable.

    One shared probe (build-once, cache-forever) for every native call site
    — data/sampler.py and data/synthetic.py dispatch through this.
    """
    global _binding, _checked
    if not _checked:
        try:
            from distributed_pytorch_example_tpu.native import binding

            _binding = binding
        except ImportError:
            if shutil.which("g++") and shutil.which("make"):
                raise  # the toolchain is here: a failed build is a bug
            _binding = None
        _checked = True
    return _binding
