"""Process start to the window's opening: import, init, compile or
cache load, fill, settling, the opening's read-back."""


def read(rec):
    return rec["spans"]["setup_s"]
