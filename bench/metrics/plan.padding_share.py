"""Share of the window's uploaded bytes that is padding (%): 1 - the bytes
holding list entries and lengths (``upload_bytes_useful``) over all
bytes uploaded (``upload_bytes``).  None where the program keeps no such
counter, or uploaded nothing."""


def read(ctx):
    if "upload_bytes" not in ctx.stats1:
        return None
    sent = ctx.delta("upload_bytes")
    if not sent:
        return None
    return 100.0 * (1.0 - ctx.delta("upload_bytes_useful") / sent)
