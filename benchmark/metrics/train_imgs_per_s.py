"""All images of all iterations the window started, over the whole
window's seconds (a fence before the first, `block_until_ready` on the
state after the last), per chip."""


def read(observed):
    if not observed.get("images") or not observed.get("window_s"):
        return None
    return observed["images"] / observed["window_s"] / observed["chips"]
