"""The quickstart's code blocks must actually run (reference tutorial
parity: the reference's docs/tutorials are what its scenario tier mirrors;
stale docs are the first thing a switching user hits).

Each ```python block from docs/quickstart.md executes in ONE shared
namespace, in order (later blocks build on earlier ones, like a reader
following along). Blocks that are deliberately illustrative fragments
(ellipses, undefined cloud endpoints) are skipped by marker.
"""

import pathlib
import re

import pytest

DOC = pathlib.Path(__file__).parents[1] / "docs" / "quickstart.md"


def _blocks():
    src = DOC.read_text()
    return re.findall(r"```python\n(.*?)```", src, re.S)


def _runnable(block: str) -> bool:
    # `<placeholder>` tokens or an explicit illustration marker mean
    # "not meant to execute standalone"; a bare `...` is valid python
    # (Ellipsis function bodies in the docs) and ordinary `<`
    # comparisons must NOT exclude a block
    return (re.search(r"<[a-z][a-z0-9_-]*>", block, re.I) is None
            and "# illustration" not in block)


def test_quickstart_blocks_execute_in_order(tmp_path):
    blocks = _blocks()
    assert len(blocks) >= 5, "quickstart lost its code blocks?"
    ns: dict = {}
    ran = 0
    for i, block in enumerate(blocks):
        if not _runnable(block):
            continue
        # environment-specific install paths → this test's sandbox (the
        # reader is told to create /var/lzy; CI must not write there)
        block = block.replace("/var/lzy", str(tmp_path))
        try:
            exec(compile(block, f"quickstart-block-{i}", "exec"), ns)  # noqa: S102
        except Exception as e:  # noqa: BLE001 — surface which block broke
            pytest.fail(f"quickstart block {i} failed: {type(e).__name__}: "
                        f"{e}\n---\n{block}")
        ran += 1
    assert ran >= 5, f"only {ran} quickstart blocks were runnable"
    # the serving block must EXECUTE (not get skipped as an illustration):
    # it is the doc surface of the inference engine (docs/serving.md)
    assert "PagedInferenceEngine" in ns, "quickstart serving block did not run"
    assert ns["req"].done
    cluster = ns.get("cluster")
    if cluster is not None:
        cluster.shutdown()
