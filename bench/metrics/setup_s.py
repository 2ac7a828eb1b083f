"""setup_s: seconds from the process's start to the window's start --
interpreter and JAX start-up, stream generation, session build, the
device loop's compilation or cache load."""


def read(view):
    return view.setup_s
