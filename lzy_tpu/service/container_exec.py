"""In-container op executor: ``python -m lzy_tpu.service.container_exec <dir>``.

The container leg of the worker's execution environment (reference
``DockerEnvironment`` runs the op process inside the image,
``lzy/execution-env/src/main/java/ai/lzy/env/base/DockerEnvironment.java:40``).
The host worker keeps the control/data planes (channels, storage, metadata) —
only the user function crosses the boundary, through an exchange directory
the runtime mounts into the container:

- ``payload.pkl`` (host → container): cloudpickled ``{func, args, kwargs}``;
- ``result.pkl`` (container → host): cloudpickled return value;
- ``error.pkl`` (container → host): cloudpickled exception with the remote
  traceback attached as a note.

Only stdlib + cloudpickle are needed inside the image; the lzy_tpu package is
bind-mounted read-only by the runtime, so arbitrary TPU images work as long
as they carry a matching python.
"""

from __future__ import annotations

import sys
import traceback

PAYLOAD = "payload.pkl"
RESULT = "result.pkl"
ERROR = "error.pkl"
ERROR_TEXT = "error.txt"   # traceback as text, for when error.pkl references
                           # classes only the image has


def main(argv=None) -> int:
    import os

    import cloudpickle

    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) != 1:
        print("usage: python -m lzy_tpu.service.container_exec <exchange_dir>",
              file=sys.stderr)
        return 2
    exchange = argv[0]
    with open(os.path.join(exchange, PAYLOAD), "rb") as f:
        payload = cloudpickle.load(f)
    try:
        result = payload["func"](*payload["args"], **payload["kwargs"])
    except BaseException as e:  # noqa: BLE001 — shipped back to the host
        tb = traceback.format_exc()
        e.add_note(f"[container traceback]\n{tb}")
        try:
            blob = cloudpickle.dumps(e)
        except Exception:
            blob = cloudpickle.dumps(RuntimeError(f"{e!r} (unpicklable)\n{tb}"))
        with open(os.path.join(exchange, ERROR), "wb") as f:
            f.write(blob)
        with open(os.path.join(exchange, ERROR_TEXT), "w") as f:
            f.write(f"{e!r}\n{tb}")
        return 1
    with open(os.path.join(exchange, RESULT), "wb") as f:
        cloudpickle.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
