"""Answers that proved correct, over the seconds of the whole window."""


def read(rec):
    w = rec["window"]
    return w["correct_answers"] / w["seconds"]
