"""Programs lowered inside the window: the program's ``jax.lower`` spans
(one per executable jax lowered, on any thread the engine's tracer
covers). Only a program that records ``serve.fetch`` records them, so
without that span the reading is None, not 0."""


def read(w):
    names = [name for name, _ in w.spans]
    if "serve.fetch" not in names:
        return None
    return float(names.count("jax.lower"))
