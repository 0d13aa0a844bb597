"""Per-role crash logs.

Parity with the reference's ``SaveErrorLog``
(``/root/reference/utils/utils.py:192-198`` + ``main.py:148-153``): any role
process that dies on an exception leaves ``logs/<role>/error_log_<ts>.txt``
with the traceback, so post-mortems don't depend on scrollback. The runner
wraps every child target with :func:`role_entry`.
"""

from __future__ import annotations

import datetime
import os
import traceback


def save_error_log(role: str, exc: BaseException, log_root: str = "logs") -> str:
    d = os.path.join(log_root, role)
    os.makedirs(d, exist_ok=True)
    ts = datetime.datetime.now().strftime("%d%m%Y_%H_%M_%S")
    path = os.path.join(d, f"error_log_{ts}.txt")
    with open(path, "w") as f:
        traceback.print_exception(exc, file=f)
    return path


def role_entry(target, role: str, log_root: str, *args) -> None:
    """mp.Process target wrapper: run ``target(*args)``; on exception, write
    the crash log and re-raise (the supervisor sees a nonzero exit)."""
    try:
        target(*args)
    except BaseException as exc:  # noqa: BLE001 — log everything, incl. SystemExit
        if not isinstance(exc, (KeyboardInterrupt, SystemExit)):
            try:
                save_error_log(role, exc, log_root)
            except OSError:
                pass  # never mask the real failure with a logging error
            try:
                # Flight recorder (tpu_rl.obs.flightrec): the role installed
                # one at startup when result_dir is set — dump its span ring
                # + config fingerprint next to the text log for post-mortems.
                from tpu_rl.obs import flightrec

                flightrec.dump_on_crash(exc)
            except Exception:
                pass  # never mask the real failure with a recorder error
        raise
