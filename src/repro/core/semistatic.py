"""Semi-static conditions — the paper's construct, adapted to JAX/TPU.

The paper (Bilokon, Lucuta & Shermer 2023) decouples *branch-changing* (expensive,
cold path: patch a relative ``jmp`` in the text segment) from *branch-taking*
(cheap, hot path: a direct call through the patched trampoline).

TPU/JAX adaptation (see DESIGN.md §2):

* branch targets      -> pre-compiled XLA executables (``jit(...).lower().compile()``)
* patched ``jmp``     -> rebinding one slot (``self._current``) to an executable
* ``branch(...)``     -> direct invocation of the current executable: no tracing,
                         no jit-cache hashing, no on-device conditional
* ``set_direction``   -> cold-path slot rebind (+ optional ``warm``: run the newly
                         selected executable on dummy inputs — the BTB-warming
                         analogue of the paper's "dummy orders")
* guard rails         -> signature/aval compatibility across branches (the paper's
                         ±2GiB displacement error) and duplicate-entry-point guard

The hot-path contract mirrors the paper's: after ``set_direction`` the call is as
cheap as calling the selected function directly — the untaken branch costs nothing,
not even HLO bytes.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import numpy as np


class BranchChangerError(RuntimeError):
    """Raised for misuse that would lead to undefined behaviour (paper §5.2)."""


# Registry of live entry points, mirroring the paper's "one instance per template
# specialisation" rule: two BranchChangers sharing a name would silently fight
# over the same entry point.
_ENTRY_POINTS: dict[str, "BranchChanger"] = {}
_REGISTRY_LOCK = threading.Lock()


def _tree_avals(tree: Any) -> Any:
    return jax.tree.map(
        lambda x: jax.api_util.shaped_abstractify(x)
        if not isinstance(x, jax.ShapeDtypeStruct)
        else x,
        tree,
    )


@dataclass
class SwitchStats:
    """Instrumentation for the paper's Fig. 11/13 analogues."""

    switches: int = 0
    compiles: int = 0
    warms: int = 0
    compile_seconds: float = 0.0
    last_switch_seconds: float = 0.0
    history: list = field(default_factory=list)


class BranchChanger:
    """N-ary semi-static condition over JAX-compiled branch targets.

    Usage (mirrors the paper's API)::

        bc = BranchChanger(if_fn, else_fn, name="order-path")
        bc.compile(example_args)            # AOT: lower+compile every branch
        bc.set_direction(True, warm=True)   # cold path
        out = bc.branch(*args)              # hot path: direct call

    ``set_direction(condition)`` with a bool selects ``if_fn`` for True (paper
    semantics); integers select the i-th branch (the switch generalisation).
    """

    def __init__(
        self,
        *branches: Callable,
        name: str | None = None,
        jit_kwargs: dict | None = None,
    ):
        if len(branches) < 2:
            raise BranchChangerError(
                "BranchChanger requires at least two branch targets (if/else)."
            )
        self._branches: tuple[Callable, ...] = branches
        self._jit_kwargs = dict(jit_kwargs or {})
        self._name = name or f"branch@{id(self):x}"
        self._compiled: list[Any] | None = None
        self._out_avals: Any = None
        self._example_args: tuple | None = None
        self._direction: int = 0
        # The "entry point": a single mutable slot. Hot path reads only this.
        self._current: Callable = branches[0]
        self._lock = threading.Lock()
        self.stats = SwitchStats()
        with _REGISTRY_LOCK:
            if self._name in _ENTRY_POINTS:
                raise BranchChangerError(
                    f"More than one BranchChanger instance for entry point "
                    f"{self._name!r}. Multiple instances sharing the same entry "
                    f"point is dangerous and results in undefined behaviour "
                    f"(paper §5.2); pass a unique name=..."
                )
            _ENTRY_POINTS[self._name] = self

    # ------------------------------------------------------------------ AOT
    def compile(self, *example_args: Any, **lower_kwargs: Any) -> "BranchChanger":
        """AOT-compile every branch target against the same abstract inputs.

        This is the analogue of the paper's requirement that all branch targets
        share one calling convention: every branch must accept the same avals
        and produce the same output avals, else the trampoline is unsound.
        """
        t0 = time.perf_counter()
        compiled = []
        out_avals = None
        for i, fn in enumerate(self._branches):
            lowered = jax.jit(fn, **self._jit_kwargs).lower(
                *_tree_avals(example_args), **lower_kwargs
            )
            shapes = jax.tree.map(
                lambda x: (tuple(x.shape), str(x.dtype)), lowered.out_info
            )
            exe = lowered.compile()
            if out_avals is None:
                out_avals = shapes
            elif shapes != out_avals:
                raise BranchChangerError(
                    f"Branch target {i} of {self._name!r} produces output avals "
                    f"{shapes} incompatible with branch 0 {out_avals}; all "
                    f"branches must share one calling convention (paper's "
                    f"displacement guard)."
                )
            compiled.append(exe)
        self._compiled = compiled
        self._out_avals = out_avals
        self._example_args = example_args
        self._current = compiled[self._direction]
        self.stats.compiles += len(compiled)
        self.stats.compile_seconds += time.perf_counter() - t0
        return self

    @property
    def is_compiled(self) -> bool:
        return self._compiled is not None

    @property
    def direction(self) -> int:
        return self._direction

    @property
    def name(self) -> str:
        return self._name

    # ----------------------------------------------------------- cold path
    def _index(self, condition: bool | int) -> int:
        if isinstance(condition, (bool, np.bool_)):
            idx = 0 if condition else 1
        else:
            idx = int(condition)
        if not 0 <= idx < len(self._branches):
            raise BranchChangerError(
                f"Direction {condition!r} out of range for "
                f"{len(self._branches)}-way branch {self._name!r}."
            )
        return idx

    def set_direction(
        self,
        condition: bool | int,
        *,
        warm: bool = False,
        warm_args: tuple | None = None,
    ) -> None:
        """Cold path: rebind the entry point; optionally warm the new target.

        The rebind itself is a single reference assignment — the Python-level
        analogue of the paper's 4-byte ``memcpy`` — and is atomic with respect
        to concurrent hot-path readers (single-writer safe without locks, the
        property the paper measures in its multi-threaded benchmark).
        """
        t0 = time.perf_counter()
        idx = self._index(condition)
        target = (
            self._compiled[idx] if self._compiled is not None else self._branches[idx]
        )
        self._direction = idx
        self._current = target  # <- the "jmp patch"
        if warm:
            self.warm(warm_args)
        self.stats.switches += 1
        self.stats.last_switch_seconds = time.perf_counter() - t0

    def set_direction_safe(self, condition: bool | int, **kw: Any) -> None:
        """Locked variant (the paper's ``-DSAFE_MODE``); strictly slower."""
        with self._lock:
            self.set_direction(condition, **kw)

    def warm(self, warm_args: tuple | None = None) -> None:
        """Run the currently selected target on dummy inputs and block.

        The analogue of the paper's BTB warming with dummy orders: the first
        call after a direction change pays one-time costs (device program
        load, host dispatch path, donation plumbing); warming pays them in the
        cold path so the hot path never observes them.
        """
        args = warm_args
        if args is None:
            if self._example_args is None:
                raise BranchChangerError(
                    f"warm() on {self._name!r} needs warm_args before compile()."
                )
            args = jax.tree.map(
                lambda a: jax.numpy.zeros(a.shape, a.dtype)
                if isinstance(a, jax.ShapeDtypeStruct)
                else jax.numpy.zeros(jax.numpy.shape(a), jax.numpy.result_type(a)),
                self._example_args,
            )
        out = self._current(*args)
        jax.block_until_ready(out)
        self.stats.warms += 1

    # ------------------------------------------------------------ hot path
    def branch(self, *args: Any) -> Any:
        """Hot path: direct call of the pre-selected target. No conditionals."""
        return self._current(*args)

    # Make the instance itself callable so it can drop into call sites.
    __call__ = branch

    # -------------------------------------------------------------- admin
    def close(self) -> None:
        with _REGISTRY_LOCK:
            _ENTRY_POINTS.pop(self._name, None)

    def __enter__(self) -> "BranchChanger":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


def reset_entry_points() -> None:
    """Test hook: forget all live entry points."""
    with _REGISTRY_LOCK:
        _ENTRY_POINTS.clear()


def live_entry_points() -> tuple[str, ...]:
    with _REGISTRY_LOCK:
        return tuple(_ENTRY_POINTS)
