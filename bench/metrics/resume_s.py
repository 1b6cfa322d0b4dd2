"""Mean over the window's resumes of the time from the kill (device
state dropped) to the end of the first step on the recovered state
(host clock)."""


def read(run):
    if run.mode != "resume" or not run.resume_times:
        return None
    return sum(run.resume_times) / len(run.resume_times)
