"""Tokens of all iterations the window started over the whole window's
seconds, per chip: `train_imgs_per_s` times the sequence length."""


def read(observed):
    if not observed.get("tokens") or not observed.get("window_s"):
        return None
    return observed["tokens"] / observed["window_s"] / observed["chips"]
