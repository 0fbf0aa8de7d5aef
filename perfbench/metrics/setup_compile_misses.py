"""Backend compiles of set-up that the persistent cache was asked for and
did not answer (``cache`` "miss" on the ``compile`` record), under the rule
of ``setup_compile_s``: 0 in a warm process, and how cold it was otherwise.
None in a program whose records carry no answer."""
from perfbench import setup_records as sr


def read(ctx):
    compiles = sr.program_compiles(ctx)
    if compiles is None:
        return None
    answers = [(r.attrs or {}).get("cache") for r in compiles
               if (r.attrs or {}).get("stage") == sr.BACKEND_COMPILE]
    return None if None in answers else float(answers.count("miss"))
