"""The load generator: no JAX in its process, and how it reads a reply."""

import os
import subprocess
import sys

from harness import manifest as mf

LOADGEN = os.path.join(mf.BENCH_DIR, "harness", "loadgen.py")


def test_importing_the_load_generator_imports_no_jax():
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('loadgen', {LOADGEN!r})\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "print('jax' in sys.modules, 'jaxlib' in sys.modules)\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["False", "False"]


def test_read_answer():
    sys.path.insert(0, os.path.dirname(LOADGEN))
    import loadgen

    class Thing:
        def __init__(self, i):
            self.tb, self.id = "item", i

    ok = {"result": [{"status": "OK", "result": [{"id": Thing(7), "d": 1.5}, {"id": "item:9", "d": 2.0}]}]}
    assert loadgen.read_answer(ok) == ("OK", [7, 9], {"d": [1.5, 2.0]})
    assert loadgen.read_answer({"result": [{"status": "OK", "result": [{"c": 41}]}]}) == ("OK", [], {"c": [41]})
    status, ids, values = loadgen.read_answer({"result": [{"status": "ERR", "result": "device launch failed"}]})
    assert status.startswith("ERR") and ids == [] and values == {}
    assert loadgen.read_answer({"error": {"message": "boom"}})[0].startswith("RPC_ERROR")
