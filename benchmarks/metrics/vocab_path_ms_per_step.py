"""Device milliseconds per step in the forward, backward and optimizer
operations of the layers that touch the vocabulary: `Embedding*` classes,
`*OutputLayer` and `*LossLayer` classes (the head's matmul and the `loss`
nested in its scope) (`harness/step_scopes.py`). None where the step names
no such layer."""

from benchmarks.harness import step_scopes


def _touches_vocabulary(cls: str) -> bool:
    return cls.startswith("Embedding") or cls.endswith(("OutputLayer",
                                                        "LossLayer"))


def read(run):
    return step_scopes.class_ms(step_scopes.table(run), _touches_vocabulary,
                                ("forward", "backward", "optimizer"))
