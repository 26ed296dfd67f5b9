"""Run graftlint without importing the ray_tpu package — or site.

``make lint`` invokes this file by path under ``python -S``:

    python -S ray_tpu/devtools/graftlint/standalone.py [args...]

Two boot taxes disappear: site processing (the same tax the worker
zygote dodges) and ``ray_tpu/__init__.py`` (which imports
core.runtime at module scope and needs site-packages). graftlint itself
is stdlib-only pure ``ast``, so ``-S`` costs nothing.

The trick: register synthetic parent packages for ``ray_tpu`` and
``ray_tpu.devtools`` (ModuleType + ``__path__``) before importing the
real graftlint subpackage — the import machinery then resolves
``ray_tpu.devtools.graftlint.*`` through the stub path entries without
ever executing the parents' ``__init__.py``. Combined with the
``.graftlint_cache/`` model cache this keeps a warm ``make lint``
under the 1.5 s budget.

Running via ``python -m ray_tpu.devtools.graftlint`` (full package
import) remains supported and identical in behavior.
"""

import sys
import types
from pathlib import Path

_REPO = Path(__file__).resolve().parents[3]


def _stub_package(name: str, path: Path) -> None:
    mod = types.ModuleType(name)
    mod.__path__ = [str(path)]
    mod.__package__ = name
    sys.modules[name] = mod


if "ray_tpu" not in sys.modules:
    _stub_package("ray_tpu", _REPO / "ray_tpu")
    _stub_package("ray_tpu.devtools", _REPO / "ray_tpu" / "devtools")

if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

from ray_tpu.devtools.graftlint.__main__ import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
