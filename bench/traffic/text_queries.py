"""Short FAQ/chat questions for the served cells, from a seeded grammar.

A copy of the query grammar in `repro.data.corpora` (its aspect
templates, synonym tables and the 40 medical and 32 job entities),
with a seeded synthetic entity space added: three-syllable names with
a medical or job suffix, so that a window of any length never runs out
of novel questions.  Each question is at most 24 tokens.

Parameters (a workload file's keys):

* ``batch``: rows per request of the closed loop;
* ``repeat_frac``: share of window rows that ask an earlier question
  again, word for word (a prefilled one or one served before in the
  stream); the rest are novel.  The share is exact: row ``i`` of the
  stream repeats where ``floor((i + 1) * repeat_frac)`` steps up, and
  the seed only shuffles the rows of each batch;
* ``prefill_rows``: novel questions served before the window, in
  batches of ``prefill_batch``;
* ``probe_rows``, ``probe_rounds``: each recall probe after the window
  asks ``probe_rows`` questions again, drawn from the last
  ``prefill_rows`` asked (about as many as the cache holds), in each of
  ``probe_rounds`` cache states;
* ``sample_rows``: window rows whose embeddings the check compares
  with the reference, drawn from the seed;
* ``entities``: size of the synthetic entity space.

Every seed gets the same sizes and the same repeats at the same
places of the stream; the seed changes only which questions are asked
and their order within a batch.
"""
from __future__ import annotations

from typing import List

import numpy as np

_PERSON = ["someone", "a person", "a patient", "an adult", "an individual"]
_FIND_OUT = ["tell", "find out", "know", "determine", "figure out"]
_BEST = ["best", "most effective", "recommended", "proven", "top"]
_WAYS = ["ways", "methods", "strategies", "approaches", "options"]

ASPECT_TEMPLATES = {
    "symptoms": ["What are the symptoms of {e}?",
                 "How can I {find} if {person} has {e}?",
                 "What signs indicate {e}?",
                 "Which warning signs point to {e}?"],
    "treatment": ["How is {e} treated?",
                  "What are the {best} {ways} to treat {e}?",
                  "What treatment options exist for {e}?",
                  "How do doctors manage {e}?"],
    "causes": ["What causes {e}?", "Why does {person} develop {e}?",
               "What are the main causes of {e}?",
               "Which factors lead to {e}?"],
    "diagnosis": ["How is {e} diagnosed?", "Which tests confirm {e}?",
                  "What is the diagnostic procedure for {e}?",
                  "How do doctors detect {e}?"],
    "prevention": ["How can {e} be prevented?",
                   "What are the {best} {ways} to prevent {e}?",
                   "How does {person} avoid developing {e}?",
                   "Which habits reduce the chance of {e}?"],
    "risk": ["What are the risk factors for {e}?",
             "Who is most at risk of {e}?",
             "Which groups are more likely to develop {e}?",
             "What raises the risk of {e}?"],
    "prognosis": ["What is the prognosis for {e}?",
                  "What is the long term outlook for {person} with {e}?",
                  "How does {e} progress over time?",
                  "What outcomes are expected with {e}?"],
    "diet": ["What diet helps with {e}?",
             "Which foods should {person} with {e} avoid?",
             "How should {person} with {e} eat?",
             "What nutrition advice applies to {e}?"],
    "howto": ["How can I become a good {e}?",
              "What should I do to be a great {e}?",
              "What are the {best} {ways} to become a {e}?",
              "How does {person} get started as a {e}?"],
    "salary": ["How much does a {e} earn?",
               "What is the typical salary of a {e}?",
               "What does a {e} get paid?",
               "What income can a {e} expect?"],
    "skills": ["What skills does a {e} need?",
               "Which abilities are essential for a {e}?",
               "What should a {e} be good at?",
               "What qualifications help a {e}?"],
    "dayinlife": ["What does a {e} do every day?",
                  "What is the daily routine of a {e}?",
                  "How does a {e} spend a typical workday?",
                  "What tasks fill a {e}'s day?"],
    "education": ["What degree do I need to become a {e}?",
                  "Which studies lead to a career as a {e}?",
                  "What education is required for a {e}?",
                  "Do I need formal training to be a {e}?"],
}

MEDICAL_ENTITIES = [
    "type 2 diabetes", "early-stage diabetes", "hypertension", "asthma",
    "myocardial infarction", "stroke", "pneumonia", "bronchitis",
    "migraine", "epilepsy", "anemia", "arthritis", "osteoporosis",
    "hypothyroidism", "hyperthyroidism", "chronic kidney disease",
    "hepatitis b", "tuberculosis", "malaria", "dengue fever",
    "ear infection", "sinusitis", "tonsillitis", "appendicitis",
    "gallstones", "peptic ulcer", "crohn disease", "ulcerative colitis",
    "psoriasis", "eczema", "glaucoma", "cataract", "sleep apnea",
    "atrial fibrillation", "heart failure", "deep vein thrombosis",
    "parkinson disease", "alzheimer disease", "multiple sclerosis",
    "stress urinary incontinence",
]
MEDICAL_ASPECTS = ["symptoms", "treatment", "causes", "diagnosis",
                   "prevention", "risk", "prognosis", "diet"]
QUORA_ENTITIES = [
    "geologist", "software engineer", "data scientist", "photographer",
    "journalist", "chef", "pilot", "architect", "lawyer", "nurse",
    "electrician", "translator", "game developer", "graphic designer",
    "teacher", "financial analyst", "marine biologist", "astronomer",
    "civil engineer", "pharmacist", "veterinarian", "screenwriter",
    "economist", "statistician", "historian", "chemist", "barista",
    "carpenter", "firefighter", "paramedic", "librarian", "geneticist",
]
QUORA_ASPECTS = ["howto", "salary", "skills", "dayinlife", "education"]

_SYLLABLES = ["ka", "lo", "mi", "ner", "tho", "vas", "ru", "pel", "dor",
              "fen", "gai", "hul", "jen", "kor", "lim", "mos", "nal", "opi",
              "qua", "ris", "sel", "tan", "ur", "vel", "wyn", "xer", "yor",
              "zan", "bri", "cal", "dex", "ep"]
_MED_SUFFIX = ["itis", "osis", "emia", "algia", "oma", "pathy",
               " syndrome", " disease", " disorder", " deficiency"]
_JOB_SUFFIX = [" engineer", " analyst", " technician", " designer",
               " therapist", " inspector", " planner", " consultant"]


def synthetic_entities(n: int, rng: np.random.Generator,
                       medical: bool) -> List[str]:
    suffix = _MED_SUFFIX if medical else _JOB_SUFFIX
    syl = rng.integers(0, len(_SYLLABLES), (n, 3))
    suf = rng.integers(0, len(suffix), n)
    return ["".join(_SYLLABLES[j] for j in s) + suffix[k]
            for s, k in zip(syl, suf)]


class TextTraffic:
    """The stream of one seed: prefill, window batches, probe."""

    def __init__(self, params: dict, seed: int):
        self.p = params
        self.rng = np.random.default_rng([int(seed) % (1 << 63), 11])
        n = int(params["entities"])
        self.entities = {
            True: MEDICAL_ENTITIES + synthetic_entities(n, self.rng, True),
            False: QUORA_ENTITIES + synthetic_entities(n, self.rng, False)}
        self.asked: List[str] = []       # every question served so far
        self.n_rows = 0                  # window rows so far

    def novel(self) -> str:
        medical = bool(self.rng.random() < 0.5)
        ents = self.entities[medical]
        aspects = MEDICAL_ASPECTS if medical else QUORA_ASPECTS
        aspect = aspects[int(self.rng.integers(len(aspects)))]
        tmpl = ASPECT_TEMPLATES[aspect]
        return tmpl[int(self.rng.integers(len(tmpl)))].format(
            e=ents[int(self.rng.integers(len(ents)))],
            person=_PERSON[int(self.rng.integers(len(_PERSON)))],
            find=_FIND_OUT[int(self.rng.integers(len(_FIND_OUT)))],
            best=_BEST[int(self.rng.integers(len(_BEST)))],
            ways=_WAYS[int(self.rng.integers(len(_WAYS)))])

    def _repeat(self) -> str:
        return self.asked[int(self.rng.integers(len(self.asked)))]

    def prefill(self) -> List[List[str]]:
        rows = [self.novel() for _ in range(int(self.p["prefill_rows"]))]
        self.asked.extend(rows)
        b = int(self.p["prefill_batch"])
        return [rows[i:i + b] for i in range(0, len(rows), b)]

    def batches(self, n: int) -> List[List[str]]:
        """The first ``n`` window batches (fixed ahead of the window)."""
        out = []
        b, frac = int(self.p["batch"]), float(self.p["repeat_frac"])
        for _ in range(n):
            i = self.n_rows + np.arange(b)
            rep = np.floor((i + 1) * frac) > np.floor(i * frac)
            self.n_rows += b
            rows = [self._repeat() if r else self.novel()
                    for r in self.rng.permutation(rep)]
            self.asked.extend(rows)
            out.append(rows)
        return out

    def probe(self) -> List[str]:
        """Recall probe: questions asked again, drawn from the last
        ``prefill_rows`` asked."""
        recent = self.asked[-int(self.p["prefill_rows"]):]
        return [recent[int(k)] for k in self.rng.integers(
            len(recent), size=int(self.p["probe_rows"]))]
