"""The harness's modules (`harness`, `tiny`) import from `bench/` and
`bench/tests/`; no test touches a chip."""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
for p in (str(HERE.parent), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)
