"""Host clock from the first dispatch's end to the window's opening:
the fill and the settling, through the tick program."""


def read(rec):
    return rec["spans"]["fill_s"]
