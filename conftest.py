"""Every test tree of this repository runs on the CPU (``tests/``, and the
benchmark's own rehearsal under ``benchmark/tests``), where a Pallas kernel
cannot be compiled: ask for the interpreter, once, for whichever tree runs.
A fixture and not an import: each tree's own ``conftest.py`` sets the
environment JAX reads before anything here imports JAX."""

import pytest


@pytest.fixture(scope="session", autouse=True)
def _pallas_kernels_interpreted():
    from lzy_tpu.ops.interpret import set_interpret

    set_interpret(True)
