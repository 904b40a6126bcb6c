"""Sum of the backend-compile events of 1 s and more during the first
dispatch (a cache load reads as a second or two)."""


def read(rec):
    return rec["spans"]["compile_s"]
