"""The model path has no user-set switch: which kernel, loss or gradient
rule runs is chosen by the code from shapes and the platform, never by an
environment variable (three such forks were measured on the chip, lost,
and were deleted with their variables in PR 28)."""
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL_PATH = ("models", "ops", "nn", "optimizer")
READS_ENV = re.compile(r"\benviron\b|\bgetenv\b")


def test_no_environment_switch_on_the_model_path():
    found = []
    for pkg in MODEL_PATH:
        for dirpath, _, names in os.walk(os.path.join(REPO, "paddle_tpu",
                                                      pkg)):
            for name in names:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                with open(path, encoding="utf-8") as f:
                    for n, line in enumerate(f, 1):
                        if READS_ENV.search(line):
                            found.append(f"{os.path.relpath(path, REPO)}:"
                                         f"{n}: {line.strip()}")
    assert not found, "\n".join(found)
