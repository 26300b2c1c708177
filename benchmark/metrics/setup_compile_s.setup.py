"""Seconds of set-up in backend compiles and loads from the persistent
compilation cache (``compile/backend`` under the program's set-up
spans: jax times both under one event).  Nothing where the ring holds
no set-up span (``setup_program_s.setup`` keeps the rule)."""
import importlib.util
import os


def read(run):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "setup_program_s.setup.py")
    spec = importlib.util.spec_from_file_location("setup_program_s", path)
    program = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(program)
    return program.seconds({"compile/backend"})
