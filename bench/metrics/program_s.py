"""Programming: seconds from the ``ModelRunner`` constructor to
``block_until_ready`` of the programmed chip's arrays, at set-up.  Moves
setup_s."""


def read(ctx):
    return ctx.split["program_s"]
