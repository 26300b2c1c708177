"""Seconds of set-up in jax tracing and lowering (``compile/trace`` +
``compile/lower`` under the program's set-up spans): every trace
counted, the cost stamp's, the signature's and the call's, less any
backend compile inside one.  Nothing where the ring holds no set-up
span (``setup_program_s.setup`` keeps the rule)."""
import importlib.util
import os


def read(run):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "setup_program_s.setup.py")
    spec = importlib.util.spec_from_file_location("setup_program_s", path)
    program = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(program)
    both = program.seconds({"compile/trace", "compile/lower",
                            "compile/backend"})
    if both is None:
        return None
    return both - program.seconds({"compile/backend"})
