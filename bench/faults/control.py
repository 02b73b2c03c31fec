"""The control: the plain reference computed in bfloat16, the nearest
precision below the float32 the configurations state, answers in place of
the program's (labels and reported modularity), for every detection."""
import numpy as np

from harness.faults import bf16_answer, each_detection


def plant():
    return each_detection(
        lambda g, labels, q: bf16_answer(g, np.asarray(labels).shape[0]))
