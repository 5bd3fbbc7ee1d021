"""ASYNC-BLOCK: no blocking calls on the event loop.

Historical bug class: ``/metrics`` rendered inline on the event loop and
``/v2/debug/*`` serialized multi-MB JSON there (fixed in PR 7 by executor
hops); ``ServerLog`` file appends called directly from async control-plane
handlers while the request paths carefully hopped to the executor.  One
blocking call on the loop stalls EVERY in-flight request for its duration
— a single synchronous device read serializes all concurrent traffic
behind the device queue it waits on.

What fires, inside ``async def`` bodies only:

* ``time.sleep`` (any import spelling) — ``await asyncio.sleep`` is the
  non-blocking sibling.
* sync file IO: the ``open`` builtin.
* sync transport clients: ``requests.*``, ``urllib.request.urlopen``,
  ``socket.socket``/``socket.create_connection``, ``subprocess.*``,
  ``os.system``.
* project-native: ``ServerLog`` emits — ``.info/.warning/.error/.verbose``
  called on a receiver whose dotted path is or ends with ``log`` (the
  ``core.log`` surface does synchronous file/stderr writes; async code
  must route through ``log_off_loop``).
* indefinite lock acquisition: non-awaited ``<x>.acquire()`` with neither
  ``blocking=False`` nor a ``timeout=`` where ``x`` names a lock.

Executor hops are recognized structurally: nested ``def``/``lambda``
bodies are skipped (that is exactly the ``run_in_executor`` idiom — the
blocking call runs on a worker, not the loop), and passing a bound method
*as an argument* (``log_off_loop(core.log.info, msg)``) is not a call.
"""

from __future__ import annotations

import ast

from .._ast_util import (awaited_ids, dotted_name, iter_body_nodes,
                         iter_functions, module_aliases, resolve_call_name)
from .._engine import Finding, Project, register_rule

#: Fully-qualified call targets that block (import-alias aware).
_BLOCKING_QUALIFIED = {
    "time.sleep": "time.sleep blocks the event loop; "
                  "use `await asyncio.sleep(...)`",
    "os.system": "os.system blocks the event loop",
    "urllib.request.urlopen": "sync HTTP on the event loop; use the aio "
                              "client or an executor hop",
    "socket.create_connection": "sync socket IO on the event loop",
    "socket.socket": "sync socket on the event loop",
    "subprocess.run": "subprocess blocks the event loop",
    "subprocess.call": "subprocess blocks the event loop",
    "subprocess.check_call": "subprocess blocks the event loop",
    "subprocess.check_output": "subprocess blocks the event loop",
    "requests.get": "sync HTTP on the event loop",
    "requests.post": "sync HTTP on the event loop",
    "requests.put": "sync HTTP on the event loop",
    "requests.delete": "sync HTTP on the event loop",
    "requests.request": "sync HTTP on the event loop",
    "requests.Session": "sync HTTP session on the event loop",
}

_LOG_METHODS = {"info", "warning", "error", "verbose"}


def _is_log_receiver(node: ast.AST) -> bool:
    """True for ``log``, ``self.log``, ``self._core.log``, ... — the
    ServerLog attribute surface."""
    d = dotted_name(node)
    return d is not None and (d == "log" or d.endswith(".log"))


def _lockish(node: ast.AST) -> bool:
    d = dotted_name(node)
    return d is not None and "lock" in d.lower()


def _acquire_bounded(call: ast.Call) -> bool:
    """``acquire(blocking=False)`` / ``acquire(timeout=...)`` /
    ``acquire(False)`` / the positional ``acquire(True, 5)`` form are all
    bounded — only the indefinite form fires."""
    for kw in call.keywords:
        if kw.arg in ("blocking", "timeout"):
            return True
    if len(call.args) >= 2:
        return True  # acquire(blocking, timeout) positional signature
    if call.args:
        first = call.args[0]
        if isinstance(first, ast.Constant) and first.value is False:
            return True
    return False


@register_rule(
    "ASYNC-BLOCK",
    "no time.sleep / sync IO / sync clients / indefinite Lock.acquire "
    "inside async def bodies (executor hops recognized)")
def check(project: Project):
    for f in project.files:
        if f.tree is None:
            continue
        mods, names = module_aliases(f.tree)
        for _cls, fn in iter_functions(f.tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            awaited = awaited_ids(fn)
            for node in iter_body_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                qual = resolve_call_name(node, mods, names)
                if qual in _BLOCKING_QUALIFIED:
                    yield Finding(
                        "ASYNC-BLOCK", f.relpath, node.lineno,
                        f"{_BLOCKING_QUALIFIED[qual]} (async def "
                        f"{fn.name})",
                        symbol=f.symbol_at(node.lineno))
                    continue
                if qual == "open" or (isinstance(node.func, ast.Name)
                                      and node.func.id == "open"):
                    yield Finding(
                        "ASYNC-BLOCK", f.relpath, node.lineno,
                        f"sync file IO (open) on the event loop (async "
                        f"def {fn.name}); hop to the executor",
                        symbol=f.symbol_at(node.lineno))
                    continue
                if isinstance(node.func, ast.Attribute):
                    attr = node.func.attr
                    if attr in _LOG_METHODS \
                            and _is_log_receiver(node.func.value):
                        yield Finding(
                            "ASYNC-BLOCK", f.relpath, node.lineno,
                            f"ServerLog .{attr}() does sync file/stderr "
                            f"IO on the event loop (async def {fn.name}); "
                            "use log_off_loop(...)",
                            symbol=f.symbol_at(node.lineno))
                        continue
                    if attr == "acquire" and id(node) not in awaited \
                            and _lockish(node.func.value) \
                            and not _acquire_bounded(node):
                        yield Finding(
                            "ASYNC-BLOCK", f.relpath, node.lineno,
                            f"indefinite Lock.acquire() on the event loop "
                            f"(async def {fn.name}); use "
                            "blocking=False/timeout= or an executor hop",
                            symbol=f.symbol_at(node.lineno))
