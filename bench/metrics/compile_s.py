"""compile_s: seconds of backend compilation during set-up, from JAX's
monitoring events (`/jax/core/compile/backend_compile_duration`)."""


def read(r, peaks):
    return getattr(r, "compile_setup_s", None)
